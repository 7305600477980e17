"""Paper Fig. 3 / Table I through the *engines*: GN-LeNet accuracy at
population scale.

Every accuracy figure so far drove the per-round host loop
(``fig3_curves`` / ``table1_accuracy``); this section is the same
Morph-vs-baselines contest run the way the paper's numbers would
actually be produced at n = 50/100 — the GN-LeNet CNN
(``configs/paper_cnn.py``, scaled by ``--width``/``--image-size``)
through the compiled superstep with device-resident data
(``DeviceDataStream``), Dirichlet(α = 0.1) class skew, and the
memory-aware exchange knobs (``mix_chunk_d`` / ``eval_batch_chunk``,
DESIGN.md §12) that keep the ``[n, n_or_k, leaf]`` mixing buffers
bounded for multi-MB params.

Emitted per population size:

* ``curve/<strategy>_n{n}/r{r}`` — convergence points with
  accuracy / loss / inter-node-variance fidelity columns;
* ``final/<strategy>_n{n}`` — final accuracy row, with the superstep's
  deterministic HLO-cost columns on the Morph rows (hard-gated by
  ``tools/check_bench.py`` against ``benchmarks/baselines/``);
* ``final/morph-sparse_n{n}`` — the same Morph workload on the sparse
  (CSR gather) engine;
* ``sharded/morph_n{n}`` — compile-only collective_bytes of the
  psum-sharded CNN superstep at ``--hlo-devices`` forced host devices
  (subprocess, same pattern as fig12);
* ``conformance/chunk_bitwise_n{n}`` — the acceptance pin: a chunked
  (``mix_chunk_d``) rerun of the dense Morph row must negotiate the
  same edges as the whole-pytree path, with params equal to f32
  rounding (``atol`` 1e-6; XLA's CPU dot kernel is chosen by operand
  shape, so bits may differ in the last ulp).  The value is 1 when the
  params are also bitwise equal; the section hard-fails on a
  divergence;
* ``acceptance/morph_ge_baselines_n{n}`` — 1 when Morph's final
  accuracy ≥ both Static and Epidemic on the non-IID split (the paper's
  Table-I ordering; meaningless at ``--smoke`` shapes).
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


def _dataset(name: str):
    """``--dataset`` parser: resolves through
    :func:`repro.configs.paper_cnn.get_cnn_config` so unknown names get
    the same "valid datasets: ..." message the library raises."""
    from repro.configs.paper_cnn import get_cnn_config
    try:
        return get_cnn_config(name)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


def _experiment(args, n: int):
    """Shared data fixture for every engine/strategy at population n:
    synthetic data with the paper CNN's class/channel counts,
    Dirichlet(α) shards, a device-resident stream, test batch."""
    from repro.data import (DeviceDataStream, dirichlet_partition,
                            make_image_classification, train_test_split)
    cfg = args.dataset
    ds = make_image_classification(
        args.samples, num_classes=cfg.num_classes,
        image_size=args.image_size, channels=cfg.in_channels,
        noise=args.noise, seed=args.seed)
    tr, te = train_test_split(ds, 0.2, seed=args.seed)
    parts = dirichlet_partition(tr.labels, n, args.alpha,
                                np.random.default_rng(args.seed))
    stream = lambda: DeviceDataStream(tr, parts, args.batch,
                                      seed=args.seed + 3)
    test = {"images": te.images[:args.test_samples],
            "labels": te.labels[:args.test_samples]}
    return stream, test


def _build(args, n: int, strategy_name: str, engine: str = "dense",
           mix_chunk_d=None, devices=None, collective="gather",
           compress="none"):
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.models.cnn import cnn_loss, cnn_params
    from repro.optim import sgd
    from repro.sparse import SparseMorphStrategy

    from .common import ExpConfig, make_ingraph_strategy
    cfg = args.dataset
    if engine == "sparse":
        strategy = SparseMorphStrategy(
            n=n, k=args.k, delta_r=args.delta_r, seed=args.seed,
            sim_row_chunk=args.sim_row_chunk)
    else:
        strategy = make_ingraph_strategy(
            strategy_name, ExpConfig(n_nodes=n, k=args.k, seed=args.seed,
                                     delta_r=args.delta_r))
    stream, test = _experiment(args, n)
    rc = dict(n_nodes=n, rounds=args.rounds, eval_every=args.eval_every,
              seed=args.seed, compiled=True, engine=engine,
              mix_chunk_d=mix_chunk_d, compress=compress,
              eval_batch_chunk=args.eval_batch_chunk)
    if devices:
        rc.update(mesh_devices=devices, collective=collective)
    return DecentralizedRunner(
        init_fn=lambda key: cnn_params(
            key, in_channels=cfg.in_channels,
            num_classes=cfg.num_classes, image_size=args.image_size,
            width=args.width),
        loss_fn=cnn_loss, eval_fn=cnn_loss, optimizer=sgd(args.lr),
        batcher=stream(), test_batch=test, strategy=strategy,
        cfg=RunnerConfig(**rc))


def _params_max_diff(a, b) -> float:
    import jax
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(flat_a, flat_b))


def _child_hlo(args, n: int) -> None:
    """Compile-only (fig12 pattern): lower the psum-sharded CNN
    superstep at the forced host device count, print HLO columns as
    CSV for the parent to record."""
    import jax
    if jax.local_device_count() < args.hlo_devices:
        print(f"fig3_accuracy_error,need_{args.hlo_devices}_devices,"
              f"have_{jax.local_device_count()}", file=sys.stderr)
        sys.exit(3)
    runner = _build(args, n, "morph", mix_chunk_d=args.mix_chunk_d,
                    devices=args.hlo_devices, collective="psum")
    hlo = harness.engine_hlo(runner._make_engine(),
                             min(args.rounds, args.eval_every))
    print(f"fig3_accuracy_hlo,morph_n{n},{json.dumps(hlo)}", flush=True)


def _sharded_hlo(args, n: int):
    env = harness.cpu_child_env(args.hlo_devices)
    argv = ["--child-hlo", "--nodes", str(n)]
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
        [sys.executable, "-m", "benchmarks.fig3_accuracy"] + argv,
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"fig3_accuracy HLO child for n={n} failed "
                           f"(exit {proc.returncode})")
    for line in proc.stdout.splitlines():
        if line.startswith("fig3_accuracy_hlo,"):
            return json.loads(line.split(",", 2)[2])
    raise RuntimeError("fig3_accuracy HLO child printed no record")


STRATEGIES = ("morph", "static", "el-oracle", "fully-connected")


def main(argv=None):
    """Engine-path accuracy reproduction rows (fig3)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", dest="dataset", type=_dataset,
                    default="cifar10",
                    help="paper CNN preset (configs/paper_cnn.py)")
    ap.add_argument("--nodes", type=int, nargs="+", default=[50],
                    help="population sizes (paper: 50 100)")
    # 150 rounds is where the paper's ordering emerges at n = 50 on the
    # default synthetic shape: at 60 rounds every k-sparse strategy is
    # still in the early transient where Epidemic's random mixing leads.
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--delta-r", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=0.1,
                    help="Dirichlet non-IID severity (paper: 0.1)")
    ap.add_argument("--width", type=int, default=8,
                    help="GN-LeNet width (paper config: 32 — scaled "
                         "down for container CPUs)")
    ap.add_argument("--image-size", type=int, default=16,
                    help="synthetic image side (paper CIFAR-10: 32)")
    ap.add_argument("--samples", type=int, default=6000)
    ap.add_argument("--test-samples", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--noise", type=float, default=3.0)
    ap.add_argument("--mix-chunk-d", type=int, default=1024,
                    help="chunked per-layer exchange cap (DESIGN.md "
                         "§12) used by the chunked conformance rerun "
                         "and the sharded lowering")
    ap.add_argument("--eval-batch-chunk", type=int, default=128)
    ap.add_argument("--sim-row-chunk", type=int, default=None)
    ap.add_argument("--hlo-devices", type=int, default=2,
                    help="forced host device count for the compile-only "
                         "psum-sharded row (<=1 disables it)")
    ap.add_argument("--strategies", nargs="+", default=list(STRATEGIES),
                    choices=STRATEGIES)
    ap.add_argument("--child-hlo", action="store_true",
                    help="internal: print sharded HLO cost in-process")
    args = ap.parse_args(argv)
    args.dataset_name = args.dataset.name.split("-")[0]

    if args.child_hlo:
        _child_hlo(args, args.nodes[0])
        return None

    bench = harness.bench("fig3_accuracy")
    finals = {}
    for n in args.nodes:
        morph = None
        for name in args.strategies:
            runner = _build(args, n, name)
            hlo = harness.engine_hlo(
                runner._make_engine(),
                min(args.rounds, args.eval_every)) \
                if name == "morph" else None
            t0 = time.time()
            log = runner.run()
            wall = time.time() - t0
            for r in log.records:
                bench.record(
                    f"curve/{name}_n{n}/r{r.rnd}",
                    f"{r.mean_accuracy:.4f}", print_csv=False,
                    fidelity={"accuracy": r.mean_accuracy,
                              "loss": r.mean_loss,
                              "internode_var": r.internode_variance})
            last = log.records[-1]
            finals[(name, n)] = last.mean_accuracy
            bench.record(
                f"final/{name}_n{n}", f"{last.mean_accuracy:.4f}",
                wall_clock_s=wall, hlo=hlo,
                shape=harness.shape_dict(runner.cfg, runner.params),
                fidelity={"accuracy": last.mean_accuracy,
                          "best_accuracy": log.best_accuracy(),
                          "loss": last.mean_loss,
                          "internode_var": last.internode_variance})
            if name == "morph":
                morph = runner

        # Acceptance pin: chunked per-layer exchange must reproduce the
        # whole-pytree Morph trajectory (dense engine): the same edges,
        # params equal to f32 rounding.
        chunked = _build(args, n, "morph", mix_chunk_d=args.mix_chunk_d)
        chunked.run()
        diff = _params_max_diff(morph.params, chunked.params)
        same_edges = len(morph.edge_history) == len(chunked.edge_history) \
            and all(np.array_equal(a, b) for a, b in
                    zip(morph.edge_history, chunked.edge_history))
        bench.record(f"conformance/chunk_bitwise_n{n}", int(diff == 0.0),
                     knobs={"mix_chunk_d": args.mix_chunk_d,
                            "eval_batch_chunk": args.eval_batch_chunk},
                     fidelity={"params_max_abs_diff": diff})
        if not same_edges or diff > 1e-6:
            raise AssertionError(
                f"chunked mixing (mix_chunk_d={args.mix_chunk_d}) "
                f"diverged from the whole-pytree path at n={n}: "
                f"same edges {same_edges}, max |diff| {diff}")

        # The same Morph contest row on the sparse (CSR gather) engine.
        runner = _build(args, n, "morph", engine="sparse",
                        mix_chunk_d=args.mix_chunk_d)
        hlo = harness.engine_hlo(runner._make_engine(),
                                 min(args.rounds, args.eval_every))
        t0 = time.time()
        log = runner.run()
        last = log.records[-1]
        finals[("morph-sparse", n)] = last.mean_accuracy
        bench.record(
            f"final/morph-sparse_n{n}", f"{last.mean_accuracy:.4f}",
            wall_clock_s=time.time() - t0, hlo=hlo,
            fidelity={"accuracy": last.mean_accuracy,
                      "loss": last.mean_loss,
                      "internode_var": last.internode_variance})

        if args.hlo_devices > 1:
            h = _sharded_hlo(args, n)
            bench.record(f"sharded/morph_n{n}",
                         f"{h['collective_bytes']:.3e}", hlo=h,
                         knobs={"devices": args.hlo_devices,
                                "collective": "psum",
                                "mix_chunk_d": args.mix_chunk_d})

        ok = (finals[("morph", n)] >= finals[("static", n)]
              and finals[("morph", n)] >= finals[("el-oracle", n)]) \
            if {"static", "el-oracle"} <= set(args.strategies) else None
        if ok is not None:
            bench.record(f"acceptance/morph_ge_baselines_n{n}", int(ok))
            bench.record(
                f"derived/morph_minus_static_n{n}",
                f"{finals[('morph', n)] - finals[('static', n)]:.4f}")
            bench.record(
                f"derived/morph_minus_el_n{n}",
                f"{finals[('morph', n)] - finals[('el-oracle', n)]:.4f}")
    bench.finish()
    return finals


if __name__ == "__main__":
    main()
