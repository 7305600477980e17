"""Compressed gossip frontier: accuracy vs bytes on the wire (DESIGN.md
§13).

The fig3 GN-LeNet Morph contest rerun under each gossip codec —
``compress`` ∈ {none, int8, fp8, int8+topk0.75, int8+topk0.25} by
default — so the accuracy cost of quantized / sparsified exchange with
error feedback is read off next to the traffic it saves.  Reuses
``fig3_accuracy``'s builder (same data fixture, same memory-aware
exchange knobs), so a codec row differs from the fig3 Morph row only
in the ``compress=`` knob.  The sweep deliberately includes
``int8+topk0.25``: at this scale (60 rounds, Dirichlet(0.1)) keeping a
quarter of the coordinates cannot propagate consensus as fast as the
shards drift apart and the contest collapses — the frontier shows the
cliff instead of hiding it, and the acceptance star sits on the safe
side of it.

Emitted per codec spec (``<spec>`` slugged, e.g. ``int8_topk0_25``):

* ``final/<spec>_n{n}`` — final accuracy, with the superstep's
  deterministic HLO-cost columns (hard-gated by ``tools/check_bench.py``
  — a codec must not regress the compiled program's cost model);
* ``bytes/<spec>_n{n}`` — total logged communication bytes: the
  engines charge the analytic wire size per transfer
  (``repro.compress.wire_bytes_tree``), so this is the codec's traffic
  claim, not a timing;
* ``sharded/<spec>_n{n}`` — compile-only ``collective_bytes`` of the
  gather-sharded superstep at ``--hlo-devices`` forced host devices
  (fig3/fig12 subprocess pattern): under the codec the gather moves the
  small wire arrays, so the frontier also shows up in the lowered
  collective traffic;
* ``derived/bytes_ratio_<spec>_n{n}`` / ``derived/acc_delta_<spec>_n{n}``
  — the frontier coordinates relative to the uncompressed row;
* ``acceptance/bytes_ge_4x_n{n}`` — 1 when the star spec
  (``int8+topk0.75``) moves ≥ 4x fewer bytes than uncompressed
  (analytic: 4 B values → 1 B codes on three quarters of the
  coordinates + a d/8 position bitmap ≈ 4.4x on the fig3 CNN);
* ``acceptance/acc_within_2pts_n{n}`` — 1 when its final accuracy is
  within 2 points of the uncompressed Morph row.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from . import harness
from .fig3_accuracy import _build, _dataset

DEFAULT_SPECS = ("none", "int8", "fp8", "int8+topk0.75",
                 "int8+topk0.25")


def _slug(spec: str) -> str:
    return spec.replace("+", "_").replace(".", "_")


def _child_hlo(args, n: int, spec: str) -> None:
    """Compile-only: lower the gather-sharded codec superstep at the
    forced host device count, print HLO columns for the parent."""
    import jax
    if jax.local_device_count() < args.hlo_devices:
        print(f"fig13_compress_error,need_{args.hlo_devices}_devices,"
              f"have_{jax.local_device_count()}", file=sys.stderr)
        sys.exit(3)
    runner = _build(args, n, "morph", mix_chunk_d=args.mix_chunk_d,
                    devices=args.hlo_devices, collective="gather",
                    compress=spec)
    hlo = harness.engine_hlo(runner._make_engine(),
                             min(args.rounds, args.eval_every))
    print(f"fig13_compress_hlo,{_slug(spec)}_n{n},{json.dumps(hlo)}",
          flush=True)


def _sharded_hlo(args, n: int, spec: str):
    env = harness.cpu_child_env(args.hlo_devices)
    argv = ["--child-hlo", "--nodes", str(n), "--compress", spec]
    for flag, val in (("--dataset", args.dataset_name),
                      ("--rounds", args.rounds), ("--seed", args.seed),
                      ("--width", args.width),
                      ("--image-size", args.image_size),
                      ("--samples", args.samples),
                      ("--eval-every", args.eval_every),
                      ("--mix-chunk-d", args.mix_chunk_d),
                      ("--hlo-devices", args.hlo_devices)):
        if val is not None:
            argv += [flag, str(val)]
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig13_compress"] + argv,
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"fig13_compress HLO child for {spec!r} "
                           f"failed (exit {proc.returncode})")
    for line in proc.stdout.splitlines():
        if line.startswith("fig13_compress_hlo,"):
            return json.loads(line.split(",", 2)[2])
    raise RuntimeError("fig13_compress HLO child printed no record")


def main(argv=None):
    """Compressed-gossip frontier rows (fig13)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", dest="dataset", type=_dataset,
                    default="cifar10")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--delta-r", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=8)
    ap.add_argument("--samples", type=int, default=1500)
    ap.add_argument("--test-samples", type=int, default=288,
                    help="gate fidelity: 96 samples put the acceptance "
                         "rows inside sampling noise (~±4 pts)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--noise", type=float, default=3.0)
    ap.add_argument("--mix-chunk-d", type=int, default=None,
                    help="chunked per-layer exchange cap for the "
                         "sharded lowering (None = whole-pytree)")
    ap.add_argument("--eval-batch-chunk", type=int, default=32)
    ap.add_argument("--sim-row-chunk", type=int, default=None)
    ap.add_argument("--hlo-devices", type=int, default=2,
                    help="forced host device count for the compile-only "
                         "gather-sharded rows (<=1 disables them)")
    ap.add_argument("--compress", nargs="+", default=list(DEFAULT_SPECS),
                    help="codec specs to sweep ('none' anchors the "
                         "derived/acceptance rows)")
    ap.add_argument("--child-hlo", action="store_true",
                    help="internal: print sharded HLO cost in-process")
    args = ap.parse_args(argv)
    args.dataset_name = args.dataset.name.split("-")[0]

    if args.child_hlo:
        _child_hlo(args, args.nodes, args.compress[0])
        return None

    bench = harness.bench("fig13_compress")
    n = args.nodes
    finals, bytes_total = {}, {}
    for spec in args.compress:
        runner = _build(args, n, "morph", compress=spec)
        hlo = harness.engine_hlo(runner._make_engine(),
                                 min(args.rounds, args.eval_every))
        t0 = time.time()
        log = runner.run()
        wall = time.time() - t0
        last = log.records[-1]
        finals[spec] = last.mean_accuracy
        bytes_total[spec] = last.comm_bytes
        bench.record(
            f"final/{_slug(spec)}_n{n}", f"{last.mean_accuracy:.4f}",
            wall_clock_s=wall, hlo=hlo, knobs={"compress": spec},
            shape=harness.shape_dict(runner.cfg, runner.params),
            fidelity={"accuracy": last.mean_accuracy,
                      "best_accuracy": log.best_accuracy(),
                      "loss": last.mean_loss,
                      "internode_var": last.internode_variance})
        bench.record(f"bytes/{_slug(spec)}_n{n}", last.comm_bytes,
                     knobs={"compress": spec})
        if args.hlo_devices > 1:
            h = _sharded_hlo(args, n, spec)
            bench.record(f"sharded/{_slug(spec)}_n{n}",
                         f"{h['collective_bytes']:.3e}", hlo=h,
                         knobs={"compress": spec,
                                "devices": args.hlo_devices,
                                "collective": "gather"})

    if "none" in finals:
        for spec in args.compress:
            if spec == "none":
                continue
            ratio = bytes_total["none"] / bytes_total[spec]
            bench.record(f"derived/bytes_ratio_{_slug(spec)}_n{n}",
                         f"{ratio:.2f}")
            bench.record(f"derived/acc_delta_{_slug(spec)}_n{n}",
                         f"{finals[spec] - finals['none']:+.4f}")
        star = "int8+topk0.75"
        if star in finals:
            bench.record(
                f"acceptance/bytes_ge_4x_n{n}",
                int(bytes_total["none"] / bytes_total[star] >= 4.0))
            bench.record(
                f"acceptance/acc_within_2pts_n{n}",
                int(finals[star] >= finals["none"] - 0.02))
    bench.finish()
    return finals


if __name__ == "__main__":
    main()
