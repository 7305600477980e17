#!/usr/bin/env python
"""Chip smoke test: the compiled Morph engine on a TPU, with the paper's
GN-LeNet at its published widths (CIFAR-10 shape: width 32, 32x32x3,
10 classes, D = 94,858 parameters per node).

One process, every phase in turn, through the entry point a user calls
(``DecentralizedRunner`` with ``RunnerConfig(compiled=True)``).  The
workload is ROADMAP's ``paper-c10``: n = 100 nodes, k = 3, negotiation
every 5 rounds, Dirichlet(0.1) class skew, batch 8 per node, a
``DeviceDataStream`` over a synthetic 50,000-image train set made from
``--seed``, 10 rounds, evaluation on 1,000 test images.  The weights are
random from the seed.

Phases (one chip, the default):

  a  dense engine, XLA mixing and similarity;
  b  the same with the Pallas kernels; the compiled program must hold
     them (``tpu_custom_call``); same round-0 edges as (a), params
     within ``ROUND0_ATOL`` of (a) after round 0 and ``FINAL_ATOL``
     after the run;
  c  sparse engine with ``SparseMorphStrategy``, XLA gather mixing and
     the Pallas sparse kernel; the two agree like (a) and (b).

``--four-chips`` runs only the sharded path users reach through
``RunnerConfig.mesh_devices``: phase (a) over 4 devices under the
"gather" and "psum" schedules, each compared with a one-device run in
this process like (b) with (a), and its params split over the 4
devices.  These runs use float32 matmul precision.

Every phase checks that loss and params are finite, that every round's
topology keeps Morph's degree bounds (in-degree exactly k for the sparse
engine; between 1 and k, with out-degree at most k, for the dense one),
and that accuracy lies in [0, 1], and
prints its compile seconds (XLA compiles or cache loads during its
first run), its steady seconds per round (a second pass over 9 rounds,
timed around ``block_until_ready``) and the device's
``peak_bytes_in_use``.  The last line of standard output is one JSON
object naming the device.  Without a TPU the script exits nonzero
before any phase.  ``--rehearse`` runs the same phases on the CPU at a
tiny size, with the Pallas kernels interpreted.

  python chip_smoke.py                  # one chip
  python chip_smoke.py --four-chips     # four chips
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --rehearse --four-chips
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.configs.paper_cnn import CIFAR10_CNN  # noqa: E402  (no JAX)

PAPER_C10 = dict(nodes=100, k=3, delta_r=5, alpha=0.1, batch=8,
                 train=50_000, test=1_000, rounds=10,
                 width=CIFAR10_CNN.width, image=CIFAR10_CNN.image_size,
                 channels=CIFAR10_CNN.in_channels,
                 classes=CIFAR10_CNN.num_classes, eval_chunk=100)
REHEARSAL = dict(PAPER_C10, nodes=12, train=1_200, test=64, width=4,
                 image=8, eval_chunk=32)
STEADY_ROUNDS = 9         # the compiled K of run()'s second superstep
# Params after round 0, two paths from the same start: one local step
# (the same program), one mix whose contractions differ only in
# rounding order: f32 rounding at the params' scale (about 1).
ROUND0_ATOL = 1e-6
# Params after 10 rounds.  SGD through ReLU and max-pool turns a last-ulp
# difference in one round's mix into an O(lr * grad) step wherever it
# flips an activation, so differences grow beyond f32 rounding: the
# sparse engine's Pallas and XLA paths ended 1.29e-3 apart on a v5e,
# the dense engine's 0.
FINAL_ATOL = 1e-2
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds and count of XLA compiles (or persistent-cache loads),
    and the count of persistent-cache hits, read from JAX's own compile
    events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1


def build_data(size, seed):
    import numpy as np

    from repro.data import (DeviceDataStream, dirichlet_partition,
                            make_image_classification)
    ds = make_image_classification(
        size["train"] + size["test"], num_classes=size["classes"],
        image_size=size["image"], channels=size["channels"], noise=3.0,
        seed=seed)
    train = ds.subset(np.arange(size["train"]))
    test = ds.subset(np.arange(size["train"], len(ds)))
    parts = dirichlet_partition(train.labels, size["nodes"], size["alpha"],
                                np.random.default_rng(seed))
    stream = DeviceDataStream(train, parts, size["batch"], seed=seed + 3)
    return stream, {"images": test.images, "labels": test.labels}


def make_runner(size, data, seed, *, sparse=False, use_pallas=False,
                interpret=False, mesh_devices=None, collective="gather"):
    """The GN-LeNet Morph run of one phase, as a user builds it."""
    from repro.core import InGraphMorphStrategy
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.models.cnn import cnn_loss, cnn_params
    from repro.optim import sgd
    from repro.sparse import SparseMorphStrategy

    n, k = size["nodes"], size["k"]
    if sparse:
        strategy = SparseMorphStrategy(n=n, k=k, delta_r=size["delta_r"],
                                       seed=seed)
    else:
        strategy = InGraphMorphStrategy(n=n, k=k, view_size=k + 2,
                                        delta_r=size["delta_r"], seed=seed)
    stream, test = data
    return DecentralizedRunner(
        init_fn=lambda key: cnn_params(
            key, in_channels=size["channels"], num_classes=size["classes"],
            image_size=size["image"], width=size["width"]),
        loss_fn=cnn_loss, eval_fn=cnn_loss, optimizer=sgd(0.05),
        batcher=stream, test_batch=test, strategy=strategy,
        cfg=RunnerConfig(
            n_nodes=n, rounds=size["rounds"], eval_every=size["rounds"],
            seed=seed, compiled=True, use_pallas=use_pallas,
            interpret=interpret, engine="sparse" if sparse else "dense",
            mesh_devices=mesh_devices, collective=collective,
            eval_batch_chunk=size["eval_chunk"]))


def run_phase(name, size, data, seed, clock, *, want_kernel=False,
              **runner_kw):
    """One ``DecentralizedRunner.run`` plus a timed steady pass; returns
    what the comparisons need (params after round 0 and after ``run``,
    the edges of every round)."""
    import jax
    import numpy as np

    n, k = size["nodes"], size["k"]
    sparse = runner_kw.get("sparse", False)
    runner = make_runner(size, data, seed, **runner_kw)

    c0, n0, h0 = clock.seconds, clock.count, clock.cache_hits
    t0 = time.perf_counter()
    evaluated = []            # params at each evaluation: rounds 0 and 9
    log = runner.run(lambda rec: evaluated.append(runner.engine.params))
    params = jax.block_until_ready(runner.params)
    first_s = time.perf_counter() - t0
    compile_s, compiles = clock.seconds - c0, clock.count - n0
    cache_hits = clock.cache_hits - h0

    leaves = jax.tree_util.tree_leaves(params)
    if not all(bool(np.isfinite(np.asarray(x)).all()) for x in leaves):
        raise AssertionError(f"{name}: non-finite params")
    for rec in log.records:
        if not np.isfinite(rec.mean_loss):
            raise AssertionError(f"{name}: loss {rec.mean_loss} at "
                                 f"round {rec.rnd}")
        acc = np.asarray(rec.per_node_accuracy)
        if not ((acc >= 0) & (acc <= 1)).all():
            raise AssertionError(f"{name}: accuracy outside [0, 1]")
    edges = [np.asarray(e, bool) for e in runner.edge_history]
    if len(edges) != size["rounds"]:
        raise AssertionError(f"{name}: {len(edges)} rounds of edges")
    # Sparse Morph fills all k slots and leaves out-degree free.  Dense
    # Morph's matching caps in- and out-degree at k: its first
    # negotiation sees only the bootstrap ring (in-degree 2), and a
    # market with k_out = k can leave a receiver short; a receiver left
    # with no sender at all fails the run.
    lo, out_max = (k, n) if sparse else (1, k)
    for rnd, e in enumerate(edges):
        deg_in, deg_out = e.sum(axis=1), e.sum(axis=0)
        if e.diagonal().any() or not (
                (lo <= deg_in) & (deg_in <= k) & (deg_out <= out_max)).all():
            raise AssertionError(
                f"{name}: round {rnd} in-degrees "
                f"{np.bincount(deg_in).tolist()}, out-degrees "
                f"{np.bincount(deg_out).tolist()}, self-loops "
                f"{int(e.diagonal().sum())}; want in-degree in "
                f"[{lo}, {k}], out-degree <= {out_max}")

    engine = runner.engine
    if want_kernel and "tpu_custom_call" not in engine.compiled_hlo(
            chunk=STEADY_ROUNDS):
        raise AssertionError(f"{name}: no Pallas kernel "
                             "(tpu_custom_call) in the compiled superstep")
    steady = []
    n_before = clock.count
    for _ in range(2):
        t0 = time.perf_counter()
        engine.run_steps(STEADY_ROUNDS, chunk=STEADY_ROUNDS)
        jax.block_until_ready(engine.params)
        steady.append((time.perf_counter() - t0) / STEADY_ROUNDS)
    if clock.count != n_before:
        raise AssertionError(f"{name}: {clock.count - n_before} compiles "
                             "inside the steady window")
    stats = jax.devices()[0].memory_stats() or {}
    last = log.records[-1]
    print(f"phase {name}: compile_s={compile_s} compiles={compiles} "
          f"cache_hits={cache_hits} "
          f"first_run_s={first_s} steady_s_per_round={steady} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')} "
          f"accuracy={last.mean_accuracy} loss={last.mean_loss} "
          f"params_per_node={sum(x.size for x in leaves) // n} "
          f"in_degree_hist_last_round="
          f"{np.bincount(edges[-1].sum(axis=1)).tolist()}",
          flush=True)
    return {"round0": evaluated[0], "params": params, "edges": edges,
            "engine": engine}


def max_diff(a, b):
    import jax
    import numpy as np
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def compare(label, a, b):
    """Same round-0 edges and params within ``ROUND0_ATOL`` after round
    0; where every round's edges agree, params within ``FINAL_ATOL``
    after the run.  Round 5 negotiates on params that differ by f32
    rounding grown over 4 SGD steps, so two correct runs may pick
    different edges there, and from then on their params are not
    comparable.  Returns what failed, or None."""
    import numpy as np
    agree = [bool(np.array_equal(x, y))
             for x, y in zip(a["edges"], b["edges"])]
    d0 = max_diff(a["round0"], b["round0"])
    d_final = max_diff(a["params"], b["params"])
    print(f"compare {label}: same_edges_per_round={agree} "
          f"params_max_abs_diff_round0={d0} (atol {ROUND0_ATOL}) "
          f"params_max_abs_diff_final={d_final} (atol {FINAL_ATOL} "
          f"{'checked' if all(agree) else 'not checked: edges differ'})",
          flush=True)
    if not (agree[0] and d0 <= ROUND0_ATOL
            and (d_final <= FINAL_ATOL or not all(agree))):
        return (f"{label}: round-0 edges equal {agree[0]}, params max "
                f"|diff| {d0} after round 0, {d_final} after the run")
    return None


def one_chip(size, data, seed, clock, interpret):
    a = run_phase("a_dense_xla", size, data, seed, clock)
    b = run_phase("b_dense_pallas", size, data, seed, clock,
                  use_pallas=True, interpret=interpret,
                  want_kernel=not interpret)
    failed = [compare("b_vs_a", b, a)]
    del a, b
    c_xla = run_phase("c_sparse_xla", size, data, seed, clock, sparse=True)
    c_pal = run_phase("c_sparse_pallas", size, data, seed, clock,
                      sparse=True, use_pallas=True, interpret=interpret,
                      want_kernel=not interpret)
    failed.append(compare("c_pallas_vs_xla", c_pal, c_xla))
    return failed


def four_chips(size, data, seed, clock):
    import jax
    if jax.device_count() < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{jax.device_count()}")
    # At the TPU's default precision the convs' bf16 passes round
    # differently for 25 and for 100 nodes per device: the sharded local
    # step was 6.15e-5 off the one-device step after round 0 on a v5e.
    # float32 matmuls leave only f32 rounding, so the comparison tests
    # the sharding and the collective schedule.
    with jax.default_matmul_precision("float32"):
        single = run_phase("a_dense_xla_1dev", size, data, seed, clock)
        failed = []
        for collective in ("gather", "psum"):
            sh = run_phase(f"a_dense_xla_4dev_{collective}", size, data,
                           seed, clock, mesh_devices=4,
                           collective=collective)
            failed.append(compare(f"{collective}_vs_1dev", sh, single))
            for leaf in jax.tree_util.tree_leaves(sh["engine"].params):
                devices = {s.device for s in leaf.addressable_shards
                           if s.data.shape[0] < leaf.shape[0]}
                if len(devices) != 4:
                    failed.append(
                        f"{collective}: a params leaf {leaf.shape} is "
                        f"split over {len(devices)} devices, not 4")
            del sh
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 4-device sharded path and its "
                         "one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size, Pallas interpreted")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1

    from repro.launch import enable_compile_cache
    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    clock = CompileClock(jax)
    size = REHEARSAL if args.rehearse else PAPER_C10
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}"
          f"  workload: {json.dumps(size)}", flush=True)
    t0 = time.perf_counter()
    data = build_data(size, args.seed)
    print(f"data_s={time.perf_counter() - t0}", flush=True)

    if args.four_chips:
        failed = four_chips(size, data, args.seed, clock)
    else:
        failed = one_chip(size, data, args.seed, clock,
                          interpret=args.rehearse)
    failed = [f for f in failed if f]
    if failed:
        raise AssertionError("; ".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
