"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --workload femnist-morph --seeds 1 2 3 \\
        --control-seeds 1 2 3 --faults half_batch node_altered \\
        --fault-seeds 1 2 3 --out readings/calibrate-femnist-morph.jsonl

In one process, for each seed: the program through the compared rounds
(set-up only, no window), the reference, and the compared numbers of the
program against it; on the control seeds, the bfloat16 reference in the
program's place; on the fault seeds, the program with each planted fault
(``chipbench/faults.py``).  One JSON line per reading, to ``--out`` and
to standard output.  Like ``run.py`` it needs the chips the cell asks
for.
"""
import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp

    from chipbench import compare, faults, harness
    cell = harness.load_cell(args.workload)
    harness.use_checkout_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("calibrate: no TPU with the cell's chips", file=sys.stderr)
        return 2
    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | set(args.fault_seeds))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as out:
        def emit(seed, kind, nums, extra=None, t=None):
            line = {"workload": args.workload, "seed": seed, "kind": kind,
                    **nums, **(extra or {}),
                    "seconds": time.perf_counter() - t}
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
            out.flush()

        for seed in seeds:
            s = harness.sub_seeds(seed)
            train, parts, test = harness.build_data(cell, s)
            t = time.perf_counter()
            ref, grad0, p0 = harness.reference_summary(
                cell, s, train, parts, test)
            emit(seed, "reference_time", {}, t=t)
            runs = []
            if seed in args.seeds:
                runs.append(("program", None))
            if seed in args.fault_seeds:
                runs.extend((f, f) for f in args.faults)
            for kind, fault in runs:
                t = time.perf_counter()
                with faults.planted(fault) if fault \
                        else contextlib.nullcontext():
                    runner = harness.make_runner(cell, s, train, parts,
                                                 test)
                    win = harness.drive(runner, cell, None)
                nums = compare.numbers(harness.program_summary(cell, p0, win),
                                       ref, grad0)
                emit(seed, kind, nums,
                     {"mean_acc_end": float(win.records[1].mean_accuracy)},
                     t=t)
                del runner, win
                gc.collect()
            if seed in args.control_seeds:
                t = time.perf_counter()
                ctrl, *_ = harness.reference_summary(
                    cell, s, train, parts, test, dtype=jnp.bfloat16)
                emit(seed, "control_bf16", compare.numbers(ctrl, ref, grad0),
                     t=t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
