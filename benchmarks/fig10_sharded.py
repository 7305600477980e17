"""Fig. 10 (repo extension): the sharded superstep at 1 vs N devices.

Runs the same n=100 Morph workload as fig9's ``compiled`` engine, but
with the node axis sharded over a device mesh (DESIGN.md §8) and the
dataset device-resident (``DeviceDataStream`` — batches drawn inside the
scan body, zero host transfer per round).  Reported per device count:

* ``rounds_per_sec`` — fused rounds per wall-clock second;
* ``per_round_ms``   — its inverse, the per-round wall-clock.

XLA fixes the device count at backend init, so each device count runs in
a **child process** on the CPU backend (``JAX_PLATFORMS=cpu``,
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); the parent
aggregates the children's CSV into ``BENCH_fig10.json`` (children print
raw lines and never write JSON themselves, so concurrent device counts
cannot clobber one file).  All simulated "devices" share the same CPU
cores, so this measures the *mechanics* (shard_map program, collective
schedule, padding), not scaling on a chip: every record says
``platform: cpu``.
"""
from __future__ import annotations

import argparse
import math
import os
import subprocess
import sys
import time

import numpy as np

from . import harness


def _mlp_params(*a, **kw):
    from repro.models.tiny import mlp_params
    return mlp_params(*a, **kw)


def _mlp_loss(p, batch):
    from repro.models.tiny import mlp_loss
    return mlp_loss(p, batch)


def _child(n: int, devices: int, rounds: int, chunk: int, k: int,
           collective: str) -> None:
    import jax
    from repro.core import InGraphMorphStrategy
    from repro.data import (DeviceDataStream, dirichlet_partition,
                            make_image_classification, train_test_split)
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.optim import sgd
    if jax.local_device_count() < devices:
        print(f"fig10_error,need_{devices}_devices,"
              f"have_{jax.local_device_count()}", file=sys.stderr)
        sys.exit(3)
    rng = np.random.default_rng(0)
    ds = make_image_classification(max(600, n * 20), num_classes=4,
                                   image_size=8, seed=0)
    tr, _ = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, n, 0.5, rng)
    runner = DecentralizedRunner(
        init_fn=_mlp_params, loss_fn=_mlp_loss, eval_fn=_mlp_loss,
        optimizer=sgd(0.05),
        batcher=DeviceDataStream(tr, parts, 4, seed=3),
        test_batch={"images": tr.images[:64], "labels": tr.labels[:64]},
        strategy=InGraphMorphStrategy(n=n, k=k, view_size=k + 2, seed=0),
        cfg=RunnerConfig(n_nodes=n, rounds=rounds, eval_every=10 ** 9,
                         sim_every=5, compiled=True, mesh_devices=devices,
                         collective=collective))
    chunk = min(chunk, rounds)
    rounds -= rounds % chunk              # whole supersteps only
    engine = runner._make_engine()
    engine.run_steps(chunk, chunk)        # compile + warm caches
    t0 = time.perf_counter()
    engine.run_steps(rounds, chunk)
    dt = time.perf_counter() - t0
    print(f"fig10,sharded-d{devices},{n},{rounds / dt:.1f}", flush=True)
    print(f"fig10_per_round_ms,d{devices}_n{n},{1e3 * dt / rounds:.2f}",
          flush=True)


def main(argv=None):
    """Sharded-superstep scaling rows (fig10)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--chunk", type=int, default=20,
                    help="superstep length (rounds per scan)")
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 8])
    ap.add_argument("--collective", default="gather",
                    choices=["gather", "psum"])
    ap.add_argument("--child", action="store_true",
                    help="internal: run one device count in-process")
    args = ap.parse_args(argv)

    if args.child:
        _child(args.nodes, args.devices[0], args.rounds, args.chunk,
               args.k, args.collective)
        return None

    bench = harness.bench("fig10")
    rps = {}
    knobs = {"chunk": args.chunk, "collective": args.collective,
             "block_d": None, "use_pallas": False, "source": "explicit",
             "platform": "cpu"}
    for d in args.devices:
        env = harness.cpu_child_env(d)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.fig10_sharded", "--child",
             "--devices", str(d), "--nodes", str(args.nodes),
             "--rounds", str(args.rounds), "--chunk", str(args.chunk),
             "--k", str(args.k), "--collective", args.collective],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        if proc.returncode != 0:
            sys.stdout.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            raise RuntimeError(f"fig10 child for {d} devices failed "
                               f"(exit {proc.returncode})")
        for line in proc.stdout.splitlines():
            if line.startswith("fig10,sharded"):
                rps[d] = float(line.rsplit(",", 1)[1])
                bench.record(
                    f"sharded-d{d}/n{args.nodes}", f"{rps[d]:.1f}",
                    rounds_per_sec=rps[d], knobs={**knobs, "devices": d})
            elif line.startswith("fig10_per_round_ms,"):
                _, key, ms = line.split(",")
                bench.record(f"per_round_ms/{key}", ms,
                             wall_clock_s=float(ms) / 1e3)
    base = args.devices[0]
    for d in args.devices[1:]:
        bench.record(f"derived/d{d}_over_d{base}_n{args.nodes}",
                     f"{rps[d] / rps[base]:.2f}")
    bench.finish()
    return rps


if __name__ == "__main__":
    main()
