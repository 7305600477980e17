"""Benchmark aggregator: one section per paper table/figure plus the
roofline + kernel microbenches.  Prints ``name,key,value`` CSV lines
and writes each section's machine-readable ``BENCH_<name>.json``
(schema: benchmarks/harness.py) into ``--bench-dir``.

  PYTHONPATH=src python -m benchmarks.run            # default sizes
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale-ish
  PYTHONPATH=src python -m benchmarks.run --smoke    # tiny CI sizes

``--smoke`` shrinks every section to minutes-scale totals — numbers are
meaningless, but every figure script executes end to end, which is what
the CI benchmarks-smoke job runs so fig scripts can't silently rot.
The CI perf job runs selected sections at smoke shapes and gates their
``BENCH_*.json`` HLO-cost columns with tools/check_bench.py.

``--junitxml PATH`` additionally writes one JUnit testcase per section
(pass/fail + duration) for CI artifact upload.

The roofline section reads dryrun_results.json (+ rerun*.json); run
``python -m repro.launch.dryrun --all --mesh both --out
dryrun_results.json`` first if missing.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def write_junit(path: str, results) -> None:
    """Minimal JUnit XML: ``results`` is [(section, seconds, error|None)]."""
    from xml.etree import ElementTree as ET
    suite = ET.Element(
        "testsuite", name="benchmarks",
        tests=str(len(results)),
        failures=str(sum(1 for _, _, e in results if e)),
        time=f"{sum(t for _, t, _ in results):.1f}")
    for name, seconds, err in results:
        case = ET.SubElement(suite, "testcase", classname="benchmarks",
                             name=name, time=f"{seconds:.1f}")
        if err:
            failure = ET.SubElement(case, "failure", message="section "
                                    "raised")
            failure.text = err
    ET.ElementTree(suite).write(path, encoding="unicode",
                                xml_declaration=True)


def main(argv=None):
    """Run the registered benchmark sections (see module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="closer-to-paper sizes (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny n/rounds: every fig script runs end to "
                         "end in minutes (the CI benchmarks-smoke job)")
    ap.add_argument("--only", default=None,
                    help="run selected sections: one name or a "
                         "comma-separated list")
    ap.add_argument("--bench-dir", default=None,
                    help="directory for BENCH_<name>.json records "
                         "(default: $BENCH_DIR, else the working "
                         "directory)")
    ap.add_argument("--junitxml", default=None,
                    help="write per-section JUnit XML here")
    args = ap.parse_args(argv)
    if args.full and args.smoke:
        print("--full and --smoke are mutually exclusive",
              file=sys.stderr)
        return 2
    if args.bench_dir is not None:
        os.environ["BENCH_DIR"] = args.bench_dir

    def size(full, default, smoke):
        return full if args.full else smoke if args.smoke else default

    rounds = size(400, 120, 10)
    nodes = size(32, 16, 6)
    # Table I: the diversity-selection advantage grows with population
    # size (paper: 50/100 nodes) — keep it above the default node count.
    t1_nodes = size(64, 32, 8)
    t1_rounds = size(400, 200, 12)

    from . import (fig2_connectivity, fig3_accuracy, fig3_curves,
                   fig4_connectivity_levels, fig5_ablation, fig67_isolation,
                   fig8_async, fig9_superstep, fig10_sharded,
                   fig11_fused_net, fig12_sparse, fig13_compress,
                   fig14_sweep, kernel_bench, roofline, table1_accuracy)

    sections = [
        ("fig2", lambda: fig2_connectivity.main(
            ["--trials", str(size(80, 40, 8))]
            + (["--sizes", "16", "32"] if args.smoke else []))),
        ("fig67", lambda: fig67_isolation.main(
            ["--rounds", str(size(60, 30, 6))]
            + (["--nodes", "24", "--ks", "3"] if args.smoke else []))),
        ("table1", lambda: table1_accuracy.main(
            ["--rounds", str(t1_rounds), "--nodes", str(t1_nodes)])),
        ("fig3", lambda: fig3_curves.main(
            ["--rounds", str(rounds), "--nodes", str(nodes)])),
        # Engine-path accuracy reproduction (GN-LeNet through the
        # compiled/sparse/sharded engines); smoke shrinks the CNN and
        # population but still exercises every engine row + the
        # chunked-exchange pin.
        ("fig3_accuracy", lambda: fig3_accuracy.main(
            ["--nodes", "50", "100", "--rounds", "150",
             "--eval-every", "25"] if args.full
            else ["--nodes", "8", "--rounds", "6", "--eval-every", "3",
                  "--width", "4", "--image-size", "8",
                  "--samples", "1500", "--test-samples", "96",
                  "--eval-batch-chunk", "32", "--mix-chunk-d", "64"]
            if args.smoke else [])),
        ("fig4", lambda: fig4_connectivity_levels.main(
            ["--rounds", str(size(rounds * 2 // 3, max(rounds * 2 // 3,
                                                       60), rounds)),
             "--nodes", str(nodes)]
            + ([] if args.full else ["--ks", "3", "5"]))),
        ("fig5", lambda: fig5_ablation.main(
            ["--rounds", str(size(rounds // 2, max(rounds // 2, 60),
                                  rounds)),
             "--nodes", str(nodes)]
            + ([] if args.full else ["--betas", "5", "500",
                                     "--deltas", "1", "25"]))),
        ("fig8", lambda: fig8_async.main(
            ["--rounds", str(size(60, 18, 6)),
             "--nodes", str(size(16, 8, 5))])),
        ("fig9", lambda: fig9_superstep.main(
            ["--rounds", str(size(150, 80, 16)),
             "--chunk", str(size(50, 50, 8))]
            + (["--nodes", "16", "50", "100"] if args.full
               else ["--nodes", "8"] if args.smoke
               else ["--nodes", "16", "50"]))),
        ("fig10", lambda: fig10_sharded.main(
            ["--rounds", str(size(60, 40, 8)),
             "--chunk", str(size(20, 20, 4))]
            + (["--nodes", "12", "--devices", "1", "2"] if args.smoke
               else ["--devices", "1", "8"]))),
        ("fig11", lambda: fig11_fused_net.main(
            ["--rounds", str(size(40, 30, 8))]
            + (["--nodes", "50", "100"] if args.full
               else ["--nodes", "6", "--profiles", "ideal", "wan",
                     "--strategies", "morph", "el-oracle"] if args.smoke
               else ["--nodes", "50"]))),
        ("fig12", lambda: fig12_sparse.main(
            ["--rounds", str(size(20, 12, 6))]
            + (["--nodes", "100", "1000", "10000"] if args.full
               else ["--nodes", "24", "--hlo-devices", "2"] if args.smoke
               else ["--nodes", "64", "256", "--hlo-devices", "4"]))),
        # Compressed-gossip frontier (accuracy vs wire/collective
        # bytes); smoke keeps the fig3 smoke CNN shape but enough rounds
        # for the within-2-points acceptance row to be meaningful.
        ("fig13_compress", lambda: fig13_compress.main(
            ["--nodes", "50", "--rounds", "150", "--eval-every", "25",
             "--width", "8", "--image-size", "16", "--samples", "6000",
             "--test-samples", "512", "--eval-batch-chunk", "128"]
            if args.full
            else ["--nodes", "8", "--rounds", "60", "--eval-every", "20",
                  "--width", "4", "--image-size", "8",
                  "--samples", "1500", "--test-samples", "288",
                  "--eval-batch-chunk", "32"] if args.smoke
            else ["--nodes", "16", "--rounds", "60",
                  "--eval-every", "20"])),
        # Sweep farm: E = seeds x profiles trajectories in one vmapped
        # dispatch, pinned bitwise against E single dispatches and timed
        # against them.  chunk=1 is the dispatch-bound shape where the
        # >=5x acceptance row holds on a single-core runner.
        ("fig14_sweep", lambda: fig14_sweep.main(
            ["--seeds", "32", "--nodes", "16", "--rounds", "48",
             "--eval-every", "24", "--timing-rounds", "48"] if args.full
            else ["--seeds", "16", "--nodes", "6", "--rounds", "24",
                  "--eval-every", "12", "--chunk", "1",
                  "--timing-rounds", "24"])),
        ("kernels", lambda: kernel_bench.main(
            ["--sizes", "65536"] if args.smoke else [])),
        ("roofline", lambda: roofline.main(["--csv"])),
    ]

    names = [name for name, _ in sections]
    only = ([s.strip() for s in args.only.split(",") if s.strip()]
            if args.only else None)
    if only:
        unknown = [s for s in only if s not in names]
        if unknown:
            print(f"unknown section(s) "
                  f"{', '.join(repr(s) for s in unknown)}; "
                  f"valid sections: {', '.join(names)}", file=sys.stderr)
            return 2

    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])

    failures = 0
    results = []
    for name, fn in sections:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"### section {name}", flush=True)
        try:
            fn()
            dt = time.time() - t0
            print(f"section_time,{name},{dt:.1f}s", flush=True)
            results.append((name, dt, None))
        except Exception:
            failures += 1
            print(f"section_FAILED,{name}", flush=True)
            traceback.print_exc()
            results.append((name, time.time() - t0,
                            traceback.format_exc()))
    if args.junitxml:
        write_junit(args.junitxml, results)
    if failures:
        print(f"benchmark_failures,{failures}", file=sys.stderr)
    return min(failures, 125)    # nonzero exit status on any failed section


if __name__ == "__main__":
    sys.exit(main())
