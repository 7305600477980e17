"""GN-LeNet CNN through the engines (the accuracy-reproduction pipeline).

Pins the paper CNN pytree the same way the tiny MLP is pinned:

* compiled == host-loop trajectories at small n (all four strategies);
* sparse compat "exact" == dense engine bitwise; sparse-native Morph
  runs end-to-end;
* sharded (1-device mesh in-process; 8 simulated devices via the slow
  spawn test) == single-device;
* **chunked per-layer exchange** (``mix_chunk_d``, DESIGN.md §12) is
  bitwise-invariant on the dense paths — for the CNN *and* for the tiny
  MLP whole-pytree anchor — and allclose-with-identical-edges on the
  sparse gather path;
* the memory-aware eval boundary (``eval_batch_chunk``) changes no
  decision, only the peak activation footprint;
* ``cnn_params`` dtype threading: a bf16 model is exactly the f32 draw
  rounded, and ``_group_norm`` rejects indivisible channel counts.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import get_cnn_config
from repro.core import (InGraphEpidemicStrategy,
                        InGraphFullyConnectedStrategy, InGraphMorphStrategy,
                        InGraphStaticStrategy, apply_mixing)
from repro.data import (DeviceDataStream, dirichlet_partition,
                        make_image_classification, train_test_split)
from repro.data.pipeline import StackedBatcher
from repro.dlrt import DecentralizedRunner, RunnerConfig
from repro.models.cnn import cnn_forward, cnn_loss, cnn_params
from repro.models.tiny import mlp_params as _mlp_params
from repro.optim import sgd
from repro.sparse import SparseMorphStrategy

N, ROUNDS = 6, 11                     # covers refreshes at 0, 5, 10
WIDTH, IMG, CLASSES = 4, 8, 4         # tiny GN-LeNet (gn groups=2 | 4)
MULTIDEV = jax.device_count() >= 2


def _init(key, dtype=jnp.float32):
    return cnn_params(key, in_channels=3, num_classes=CLASSES,
                      image_size=IMG, width=WIDTH, dtype=dtype)


def _data():
    ds = make_image_classification(400, num_classes=CLASSES,
                                   image_size=IMG, seed=0)
    return train_test_split(ds, 0.25)


def _runner(strategy, *, compiled=True, stream=False, rounds=ROUNDS,
            **cfg_kw):
    tr, te = _data()
    parts = dirichlet_partition(tr.labels, N, 0.5,
                                np.random.default_rng(0))
    batcher = (DeviceDataStream(tr, parts, 8, seed=3) if stream
               else StackedBatcher(tr, parts, 8, seed=3))
    return DecentralizedRunner(
        init_fn=_init, loss_fn=cnn_loss, eval_fn=cnn_loss,
        optimizer=sgd(0.05), batcher=batcher,
        test_batch={"images": te.images, "labels": te.labels},
        strategy=strategy,
        cfg=RunnerConfig(n_nodes=N, rounds=rounds, eval_every=5,
                         compiled=compiled, **cfg_kw))


STRATEGIES = {
    "morph": lambda: InGraphMorphStrategy(n=N, k=2, view_size=4, seed=0),
    "static": lambda: InGraphStaticStrategy(n=N, degree=3, seed=0),
    "epidemic": lambda: InGraphEpidemicStrategy(n=N, k=2, seed=0),
    "fully-connected": lambda: InGraphFullyConnectedStrategy(n=N),
}


def _assert_conformant(a, b, atol=1e-5):
    assert len(a.edge_history) == len(b.edge_history)
    for r, (ea, eb) in enumerate(zip(a.edge_history, b.edge_history)):
        assert np.array_equal(ea, eb), f"edge sequence diverged at {r}"
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)
    assert len(a.log.records) == len(b.log.records)
    for ra, rb in zip(a.log.records, b.log.records):
        assert ra.rnd == rb.rnd
        assert ra.comm_bytes == rb.comm_bytes
        assert ra.isolated == rb.isolated
        assert ra.mean_accuracy == pytest.approx(rb.mean_accuracy,
                                                 abs=1e-5)


def _assert_params_bitwise(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Engine conformance matrix on the CNN pytree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_cnn_compiled_matches_host_loop(name):
    host = _runner(STRATEGIES[name](), compiled=False)
    host.run()
    comp = _runner(STRATEGIES[name](), compiled=True)
    comp.run()
    _assert_conformant(host, comp)


def test_cnn_sparse_compat_exact_bitwise_vs_dense():
    dense = _runner(STRATEGIES["morph"]())
    dense.run()
    sparse = _runner(STRATEGIES["morph"](), engine="sparse")
    sparse.run()
    for ea, eb in zip(dense.edge_history, sparse.edge_history):
        assert np.array_equal(ea, eb)
    _assert_params_bitwise(dense, sparse)


def test_cnn_sharded_one_device_matches_host_loop():
    host = _runner(STRATEGIES["morph"](), compiled=False)
    host.run()
    sh = _runner(STRATEGIES["morph"](), compiled=True, mesh_devices=1)
    sh.run()
    _assert_conformant(host, sh)


@pytest.mark.skipif(not MULTIDEV, reason="needs >= 2 devices (run via "
                    "test_spawn_cnn_multi_device)")
def test_multidev_cnn_sharded_matches_single():
    """Sharded CNN == single-device compiled, node padding exercised
    (n=6 over 8 devices pads to 8), device-stream data layout."""
    single = _runner(STRATEGIES["morph"](), compiled=True, stream=True)
    single.run()
    sh = _runner(STRATEGIES["morph"](), compiled=True, stream=True,
                 mesh_devices=jax.device_count())
    sh.run()
    _assert_conformant(single, sh)


@pytest.mark.slow
def test_spawn_cnn_multi_device():
    """Re-run this file's _multidev test on 8 simulated host devices."""
    if MULTIDEV:
        pytest.skip("already multi-device; _multidev tests ran directly")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         __file__, "-k", "multidev"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, \
        f"multi-device run failed:\n{proc.stdout}\n{proc.stderr}"
    assert " passed" in proc.stdout


# ---------------------------------------------------------------------------
# Chunked per-layer exchange (DESIGN.md §12)
# ---------------------------------------------------------------------------

def test_apply_mixing_chunked_bitwise_on_tiny_mlp():
    """The conformance anchor: chunked per-layer mixing == the existing
    whole-pytree contraction, bit for bit, on the tiny MLP."""
    keys = jax.random.split(jax.random.PRNGKey(0), N)
    tree = jax.vmap(_mlp_params)(keys)
    rng = np.random.default_rng(1)
    w = rng.random((N, N))
    w = jnp.asarray(w / w.sum(axis=1, keepdims=True), jnp.float32)
    ref = apply_mixing(w, tree)
    for chunk in (1, 7, 64, 10_000):
        out = apply_mixing(w, tree, chunk_d=chunk)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), chunk


@pytest.mark.parametrize("chunk", [37, 512])
def test_cnn_dense_engine_chunk_invariant(chunk):
    """mix_chunk_d never changes a dense-engine CNN trajectory — same
    bits, only the mixing buffer footprint."""
    ref = _runner(STRATEGIES["morph"]())
    ref.run()
    ch = _runner(STRATEGIES["morph"](), mix_chunk_d=chunk)
    ch.run()
    for ea, eb in zip(ref.edge_history, ch.edge_history):
        assert np.array_equal(ea, eb)
    _assert_params_bitwise(ref, ch)


def test_cnn_sparse_native_chunk_invariant():
    """Sparse-native Morph under mix_chunk_d + sim_row_chunk: identical
    negotiated edges (row-chunked Eq.-3 is exact), params allclose (the
    gather mix is last-ulp sensitive to XLA fusion across chunkings)."""
    ref = _runner(SparseMorphStrategy(n=N, k=2, seed=0), engine="sparse")
    ref.run()
    ch = _runner(SparseMorphStrategy(n=N, k=2, seed=0, sim_row_chunk=2),
                 engine="sparse", mix_chunk_d=37)
    ch.run()
    assert len(ref.edge_history) == len(ch.edge_history)
    for r, (ea, eb) in enumerate(zip(ref.edge_history, ch.edge_history)):
        assert np.array_equal(ea, eb), f"edge sequence diverged at {r}"
    for x, y in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(ch.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5)


def test_cnn_device_stream_chunk_invariant():
    """The full memory-aware configuration (device stream + chunked
    mixing + chunked eval + superstep cap) draws the same batches and
    walks the same dense-engine trajectory."""
    ref = _runner(STRATEGIES["morph"](), stream=True)
    ref.run()
    ch = _runner(STRATEGIES["morph"](), stream=True, mix_chunk_d=64,
                 eval_batch_chunk=16, chunk=2)
    ch.run()
    for ea, eb in zip(ref.edge_history, ch.edge_history):
        assert np.array_equal(ea, eb)
    _assert_params_bitwise(ref, ch)
    for ra, rb in zip(ref.log.records, ch.log.records):
        assert ra.mean_accuracy == pytest.approx(rb.mean_accuracy,
                                                 abs=1e-5)
        assert ra.mean_loss == pytest.approx(rb.mean_loss, abs=1e-5)


def test_eval_batch_chunk_weighted_combine():
    """make_evaluator(batch_chunk) == the whole-batch pass to f32
    tolerance, including a ragged final chunk."""
    from repro.dlrt.runtime import make_evaluator
    keys = jax.random.split(jax.random.PRNGKey(0), N)
    params = jax.vmap(_init)(keys)
    tr, te = _data()
    test = {"images": jnp.asarray(te.images),
            "labels": jnp.asarray(te.labels)}
    ref_l, ref_m = make_evaluator(cnn_loss)(params, test)
    for chunk in (7, 32, 10_000):
        l, m = make_evaluator(cnn_loss, batch_chunk=chunk)(params, test)
        np.testing.assert_allclose(np.asarray(l), np.asarray(ref_l),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(m["accuracy"]),
                                   np.asarray(ref_m["accuracy"]),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# Model/config satellites
# ---------------------------------------------------------------------------

def test_cnn_params_dtype_threaded():
    """dtype reaches every leaf, and a bf16 model is exactly the f32
    draw rounded — the same random stream regardless of storage dtype."""
    key = jax.random.PRNGKey(0)
    p32 = _init(key)
    pbf = _init(key, dtype=jnp.bfloat16)
    for a, b in zip(jax.tree_util.tree_leaves(p32),
                    jax.tree_util.tree_leaves(pbf)):
        assert a.dtype == jnp.float32
        assert b.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a.astype(jnp.bfloat16)),
                              np.asarray(b))


def test_group_norm_rejects_indivisible_channels():
    p = cnn_params(jax.random.PRNGKey(0), in_channels=3, num_classes=4,
                   image_size=IMG, width=3)       # 3 channels, 2 groups
    x = jnp.zeros((2, IMG, IMG, 3))
    with pytest.raises(ValueError, match="divisible"):
        cnn_forward(p, x)


def test_get_cnn_config_names_valid_datasets():
    assert get_cnn_config("cifar10").in_channels == 3
    assert get_cnn_config("femnist").num_classes == 62
    with pytest.raises(ValueError, match="cifar10.*femnist"):
        get_cnn_config("imagenet")


# ---------------------------------------------------------------------------
# Slow tier: paper-scale n
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cnn_n50_chunk_and_stream_invariant():
    """n=50 Dirichlet(0.1) CNN through the dense engine: the chunked
    memory-aware configuration negotiates the same edges as the plain
    one at the paper's population scale, with params equal to f32
    rounding.  Not bitwise: XLA's CPU backend picks its dot kernel by
    operand shape, so a [50, 50] x [50, 256] chunk can round the same
    length-50 sums differently in the last ulp from the whole-leaf dot
    (measured max |diff| 2.2e-8 after 6 rounds)."""
    n = 50
    # 10 classes like CIFAR-10: with fewer classes a Dirichlet(0.1)
    # node's total share rounds to zero too often to satisfy
    # min_per_node at n=50.
    ds = make_image_classification(2000, num_classes=10,
                                   image_size=IMG, seed=0)
    tr, te = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, n, 0.1,
                                np.random.default_rng(0))
    init = lambda key: cnn_params(key, in_channels=3, num_classes=10,
                                  image_size=IMG, width=WIDTH)

    def build(**cfg_kw):
        return DecentralizedRunner(
            init_fn=init, loss_fn=cnn_loss, eval_fn=cnn_loss,
            optimizer=sgd(0.05),
            batcher=DeviceDataStream(tr, parts, 8, seed=3),
            test_batch={"images": te.images, "labels": te.labels},
            strategy=InGraphMorphStrategy(n=n, k=3, view_size=5, seed=0),
            cfg=RunnerConfig(n_nodes=n, rounds=6, eval_every=5,
                             compiled=True, **cfg_kw))
    ref = build()
    ref.run()
    ch = build(mix_chunk_d=256, eval_batch_chunk=64)
    ch.run()
    assert len(ref.edge_history) == len(ch.edge_history)
    for ea, eb in zip(ref.edge_history, ch.edge_history):
        assert np.array_equal(ea, eb)
    for x, y in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(ch.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# Compressed gossip on the CNN pytree (DESIGN.md §13)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_cnn_compress_none_bitwise(name):
    """compress="none" traces the identical program on the GN-LeNet
    pytree — bitwise params, same edges, same comm bytes."""
    ref = _runner(STRATEGIES[name]())
    ref.run()
    non = _runner(STRATEGIES[name](), compress="none")
    non.run()
    for r, (ea, eb) in enumerate(zip(ref.edge_history, non.edge_history)):
        assert np.array_equal(ea, eb), f"edges diverged at round {r}"
    _assert_params_bitwise(ref, non)
    assert [rec.comm_bytes for rec in ref.log.records] == \
        [rec.comm_bytes for rec in non.log.records]


def test_cnn_compress_int8_close_to_uncompressed():
    """int8 row on the multi-leaf CNN tree: identical negotiated edges,
    params within the per-leaf quantization band.  Each leaf carries
    its own per-row scale, so the error bound tracks the largest leaf
    magnitude (GroupNorm scales ~ 1.0 -> step/2 ~ 4e-3); atol = 1.5e-2
    keeps ~3x headroom over the measured deviation."""
    ref = _runner(STRATEGIES["morph"]())
    ref.run()
    q = _runner(STRATEGIES["morph"](), compress="int8")
    q.run()
    for r, (ea, eb) in enumerate(zip(ref.edge_history, q.edge_history)):
        assert np.array_equal(ea, eb), f"edges diverged at round {r}"
    for x, y in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(q.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1.5e-2)
    ratio = (ref.log.records[-1].comm_bytes
             / q.log.records[-1].comm_bytes)
    assert 3.5 < ratio < 4.0
