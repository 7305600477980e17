"""Fig. 12 (repo extension): dense vs sparse engine scaling.

The sparse subsystem's headline claim (DESIGN.md §11) is that per-round
cost scales O(nk·D) instead of O(n²·D).  This figure runs the same
Morph workload through both engines at n in {100, 1k, 10k}:

* ``dense``  — ``CompiledSuperstep`` with ``InGraphMorphStrategy``:
  [n,n] similarity, dense row-stochastic mixing;
* ``sparse`` — ``RunnerConfig(engine="sparse")`` with
  ``SparseMorphStrategy``: [n,k] CSR adjacency carried in the scan,
  gossiped candidate discovery, gather + einsum mixing.

Reported per population size:

* per-round wall-clock (``rounds_per_sec`` / ``per_round_ms``) — each
  timed measurement is ONE compiled dispatch (``run_steps(rounds,
  rounds)``), so the n = 10^4 sparse row demonstrates a whole-population
  superstep completing in a single device program.  Dense rows above
  ``--dense-max`` are cost-model only (an O(n²·D) CPU einsum at n = 10^4
  would take minutes per round — exactly the wall this figure measures).
* ``collective_bytes`` of the psum-sharded program — compile-only, in a
  child process with ``--xla_force_host_platform_device_count`` (XLA
  pins the device count at backend init; same pattern as fig10).  The
  sparse neighbor-only schedule (``psum_scatter`` of the local partial
  sums) is where the O(n²) -> O(nk) drop shows up.
* ``derived/sparse_over_dense_n*`` (wall-clock speedup),
  ``derived/flops_drop_n*`` and ``derived/collective_drop_n*`` (HLO
  cost ratios), and ``derived/crossover_n`` — the smallest measured n
  where the sparse engine's throughput beats the dense engine's: the
  crossover the autotuner's ``engine`` knob resolves per shape.

The HLO-cost columns land in ``BENCH_fig12.json`` and are hard-gated by
``tools/check_bench.py`` in the CI perf job; wall-clock stays warn-only.
"""
from __future__ import annotations

import argparse
import json
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


def _fixture(n: int, seed: int = 0):
    """Device-resident data fixture that scales to n = 10^4: equal
    ``np.array_split`` shards so every node owns >= 1 sample (Dirichlet
    hands out empty shards at large n, which the batcher rejects), and
    a dataset sized ~2 samples/node so the device-resident shard table
    stays small."""
    from repro.data import make_image_classification, train_test_split
    ds = make_image_classification(max(600, 2 * n), num_classes=4,
                                   image_size=8, seed=seed)
    tr, _ = train_test_split(ds, 0.25)
    parts = np.array_split(np.arange(len(tr.labels)), n)
    return tr, parts


def _build(n: int, k: int, engine: str, rounds: int, devices: int = 1,
           collective: str = "gather"):
    from repro.core import InGraphMorphStrategy
    from repro.data import DeviceDataStream
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.optim import sgd
    from repro.sparse import SparseMorphStrategy
    tr, parts = _fixture(n)
    if engine == "sparse":
        strategy = SparseMorphStrategy(n=n, k=k, delta_r=5, seed=0)
    else:
        strategy = InGraphMorphStrategy(n=n, k=k, view_size=k + 2,
                                        delta_r=5, seed=0)
    cfg = dict(n_nodes=n, rounds=rounds, eval_every=10 ** 9, sim_every=5,
               compiled=True, engine=engine)
    if devices > 1:
        cfg.update(mesh_devices=devices, collective=collective)
    return DecentralizedRunner(
        init_fn=_mlp_params, loss_fn=_mlp_loss, eval_fn=_mlp_loss,
        optimizer=sgd(0.05),
        batcher=DeviceDataStream(tr, parts, 2, seed=3),
        test_batch={"images": tr.images[:64], "labels": tr.labels[:64]},
        strategy=strategy, cfg=RunnerConfig(**cfg))


def _time_one_dispatch(engine, rounds: int, repeats: int) -> float:
    """Rounds/sec with the whole run fused into ONE compiled dispatch
    (chunk == rounds); first call compiles + warms, best-of-N timed."""
    engine.run_steps(rounds, rounds)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        engine.run_steps(rounds, rounds)
        best = min(best, time.perf_counter() - t0)
    return rounds / best


def _child_hlo(n: int, k: int, rounds: int, devices: int) -> None:
    """Compile-only: lower the psum-sharded superstep for both engines
    at the forced device count and print the HLO-cost columns as CSV
    (the parent records them; children never write JSON)."""
    import jax
    if jax.local_device_count() < devices:
        print(f"fig12_error,need_{devices}_devices,"
              f"have_{jax.local_device_count()}", file=sys.stderr)
        sys.exit(3)
    for engine in ("dense", "sparse"):
        runner = _build(n, k, engine, rounds, devices=devices,
                        collective="psum")
        hlo = harness.engine_hlo(runner._make_engine(), rounds)
        print(f"fig12_hlo,{engine}_n{n},{json.dumps(hlo)}", flush=True)


def _sharded_hlo(n: int, k: int, rounds: int, devices: int):
    """Run :func:`_child_hlo` in a subprocess with the forced host
    device count; returns {engine: hlo_dict}."""
    env = harness.cpu_child_env(devices)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig12_sparse", "--child-hlo",
         "--nodes", str(n), "--k", str(k), "--rounds", str(rounds),
         "--hlo-devices", str(devices)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"fig12 HLO child for n={n} failed "
                           f"(exit {proc.returncode})")
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("fig12_hlo,"):
            _, key, payload = line.split(",", 2)
            out[key.split("_n")[0]] = json.loads(payload)
    return out


def main(argv=None):
    """Sparse-engine scaling rows (fig12)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, nargs="+",
                    default=[100, 1000, 10000])
    ap.add_argument("--rounds", type=int, default=20,
                    help="rounds per run == rounds per compiled "
                         "dispatch (the whole run is one superstep)")
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--dense-max", type=int, default=1000,
                    help="largest n the dense engine is wall-clock "
                         "timed at; above this it is HLO-cost only")
    ap.add_argument("--hlo-devices", type=int, default=8,
                    help="forced host device count for the psum-sharded "
                         "collective_bytes comparison (1 disables it)")
    ap.add_argument("--child-hlo", action="store_true",
                    help="internal: print sharded HLO cost in-process")
    args = ap.parse_args(argv)

    if args.child_hlo:
        _child_hlo(args.nodes[0], args.k, args.rounds, args.hlo_devices)
        return None

    bench = harness.bench("fig12")
    rps = {}
    flops = {}
    for n in args.nodes:
        repeats = 3 if n <= 200 else 1
        for engine in ("dense", "sparse"):
            runner = _build(n, args.k, engine, args.rounds)
            eng = runner._make_engine()
            hlo = harness.engine_hlo(eng, args.rounds)
            flops[(engine, n)] = hlo["flops"]
            if engine == "dense" and n > args.dense_max:
                bench.record(f"hlo_only/dense_n{n}",
                             f"{hlo['flops']:.3e}", hlo=hlo,
                             shape=harness.shape_dict(runner.cfg,
                                                      runner.params))
                continue
            r = _time_one_dispatch(eng, args.rounds, repeats)
            rps[(engine, n)] = r
            bench.record(
                f"throughput/{engine}_n{n}", f"{r:.1f}",
                rounds_per_sec=r, hlo=hlo,
                shape=harness.shape_dict(runner.cfg, runner.params),
                knobs=harness.knobs_dict(runner.cfg,
                                         runner.resolved_knobs),
                dispatches=1, rounds_per_dispatch=args.rounds)
            bench.record(f"per_round_ms/{engine}_n{n}",
                         f"{1e3 / r:.2f}", wall_clock_s=1.0 / r)
        if ("dense", n) in rps:
            bench.record(f"derived/sparse_over_dense_n{n}",
                         f"{rps[('sparse', n)] / rps[('dense', n)]:.2f}")
        bench.record(f"derived/flops_drop_n{n}",
                     f"{flops[('dense', n)] / flops[('sparse', n)]:.1f}")
        if args.hlo_devices > 1:
            sharded = _sharded_hlo(n, args.k, args.rounds,
                                   args.hlo_devices)
            for engine in ("dense", "sparse"):
                h = sharded[engine]
                bench.record(f"collective/{engine}_n{n}",
                             f"{h['collective_bytes']:.3e}", hlo=h,
                             knobs={"devices": args.hlo_devices,
                                    "collective": "psum",
                                    "chunk": args.rounds})
            drop = (sharded["dense"]["collective_bytes"]
                    / max(sharded["sparse"]["collective_bytes"], 1))
            bench.record(f"derived/collective_drop_n{n}", f"{drop:.1f}")
    crossover = next((n for n in sorted(args.nodes)
                      if ("dense", n) in rps
                      and rps[("sparse", n)] > rps[("dense", n)]), None)
    bench.record("derived/crossover_n",
                 str(crossover) if crossover else "none")
    bench.finish()
    return rps


if __name__ == "__main__":
    main()
