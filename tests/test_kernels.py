"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True
executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                              # only the property test needs hypothesis
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:               # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.kernels import ops, ref

# Includes the shapes the compiled superstep engine actually feeds the
# kernels: n not a multiple of the sublane tile (7, 33, 50), odd D
# requiring block padding (300, 8192+7, 129).
SHAPES = [(4, 64), (8, 1000), (16, 8192), (33, 300), (16, 8192 + 7),
          (7, 129), (50, 1000)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_pairwise_cosine_sweep(n, d, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(n + d), (n, d))
         .astype(dtype))
    got = ops.pairwise_cosine(x, interpret=True)
    want = ref.pairwise_cosine_ref(x)
    atol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol)
    np.testing.assert_allclose(np.diag(np.asarray(got)), 1.0, atol=atol)


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_graph_mix_sweep(n, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * 7 + d))
    x = jax.random.normal(k1, (n, d)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(k2, (n, n)))
    got = ops.mix(w, x, interpret=True)
    want = ref.graph_mix_ref(w, x)
    atol = 1e-4 * np.sqrt(n) if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("m,n,d", [(1, 8, 512), (3, 10, 300),
                                   (13, 104, 1000), (6, 6, 129)])
def test_graph_mix_rectangular_row_block(m, n, d):
    """Sharded-superstep shape: each device applies its [n_local, n_pad]
    row block of W to the gathered [n_pad, D] population; padding is
    per-shard (m and n tile independently) and results match the same
    rows of the square product."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 31 + n))
    x = jax.random.normal(k1, (n, d))
    w_full = jax.nn.softmax(jax.random.normal(k2, (n, n)))
    got = ops.mix(w_full[:m], x, interpret=True)
    want = ref.graph_mix_ref(w_full, x)[:m]
    assert got.shape == (m, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * np.sqrt(n))


@pytest.mark.parametrize("n,d", [(8, 512), (16, 2048), (7, 129),
                                 (33, 300), (50, 1000)])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_graph_mix_masked_fused(n, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (n, d)).astype(dtype)
    edges = jax.random.bernoulli(k2, 0.3, (n, n))
    got = ops.mix_masked(edges, x, interpret=True)
    want = ref.graph_mix_masked_ref(edges, x)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# block-sparse graph_mix (CSR gather-tiles-then-MAC) vs the dense kernel
# ---------------------------------------------------------------------------

def _random_csr(seed, n, k):
    """[n, k] distinct non-self senders + row-stochastic (w, w_self)."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice([j for j in range(n) if j != i],
                               size=k, replace=False)
                    for i in range(n)]).astype(np.int32)
    raw = rng.random((n, k + 1)).astype(np.float32) + 0.1
    raw /= raw.sum(axis=1, keepdims=True)
    return (jnp.asarray(idx), jnp.asarray(raw[:, :k]),
            jnp.asarray(raw[:, k]))


def _csr_to_dense(idx, w, w_self, n):
    dense = np.zeros((n, n), np.float32)
    np.add.at(dense, (np.repeat(np.arange(n), idx.shape[1]),
                      np.asarray(idx).ravel()), np.asarray(w).ravel())
    dense[np.arange(n), np.arange(n)] += np.asarray(w_self)
    return jnp.asarray(dense)


# Sweep covers the engine's awkward shapes: n % 8 != 0 (row padding with
# own-row parked tail indices), odd D (D-block padding), and k from
# barely-sparse to the fig12 operating point k=8.
@pytest.mark.parametrize("n,d", [(8, 256), (33, 300), (7, 129),
                                 (50, 1000), (16, 8192 + 7)])
@pytest.mark.parametrize("k", [2, 3, 8])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_graph_mix_sparse_parity_vs_dense_mix(n, d, k, dtype):
    if k >= n:
        pytest.skip("k must stay below n")
    x = jax.random.normal(jax.random.PRNGKey(n * 13 + d + k),
                          (n, d)).astype(dtype)
    idx, w, w_self = _random_csr(n + k, n, k)
    got = ops.mix_sparse(idx, w, w_self, x, interpret=True)
    want = ops.mix(_csr_to_dense(idx, w, w_self, n), x, interpret=True)
    atol = 1e-4 * np.sqrt(k + 1) if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_mix_sparse_mask_parks_invalid_slots():
    """Masked slots contribute nothing, whatever garbage idx/w carry."""
    n, d, k = 9, 64, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    idx, w, w_self = _random_csr(3, n, k)
    mask = jnp.asarray(np.random.default_rng(4).random((n, k)) < 0.5)
    w_valid = jnp.where(mask, w, 0.0)
    want = ops.mix(_csr_to_dense(idx, w_valid, w_self, n), x,
                   interpret=True)
    trash_idx = jnp.where(mask, idx, n - 1)
    trash_w = jnp.where(mask, w, 7.5)
    got = ops.mix_sparse(trash_idx, trash_w, w_self, x, mask=mask,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


def test_mix_sparse_xla_fallback_matches_kernel():
    """There is no silent fallback: interpret=False off TPU raises.  The
    XLA gather path the engine selects with use_pallas=False
    (repro.sparse.mix) gives the Pallas body's numbers to f32
    tolerance."""
    from repro.sparse import SparseAdjacency
    from repro.sparse.mix import sparse_mix_rows
    n, d, k = 16, 512, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    idx, w, w_self = _random_csr(7, n, k)
    kern = ops.mix_sparse(idx, w, w_self, x, interpret=True)
    adj = SparseAdjacency(idx=idx, w=w, w_self=w_self,
                          mask=jnp.ones((n, k), bool))
    xla = sparse_mix_rows(adj, x)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(xla),
                               atol=1e-5)
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="interpret=True"):
            ops.mix_sparse(idx, w, w_self, x, interpret=False)


def test_mix_sparse_pytree_matches_engine_gather_path():
    """ops.mix_sparse_pytree (the engine's Pallas sparse mixing) ==
    repro.sparse.mix.sparse_mix_pytree (the pure-jnp path)."""
    from repro.sparse import SparseAdjacency, sparse_mix_pytree
    n, k = 10, 3
    idx, w, w_self = _random_csr(11, n, k)
    adj = SparseAdjacency(idx=idx, w=w, w_self=w_self,
                          mask=jnp.ones((n, k), bool))
    tree = {"a": jax.random.normal(jax.random.PRNGKey(12), (n, 9, 3)),
            "b": jax.random.normal(jax.random.PRNGKey(13), (n, 17))}
    got = ops.mix_sparse_pytree(idx, w, w_self, tree, mask=adj.mask,
                                interpret=True)
    want = sparse_mix_pytree(adj, tree)
    for key in tree:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]), atol=1e-5)


def test_mix_masked_pytree_matches_uniform_mixing():
    """The compiled engine's fused mixing path == uniform_weights + mix."""
    from repro.core import apply_mixing, uniform_weights_jax
    n = 6
    edges = jax.random.bernoulli(jax.random.PRNGKey(6), 0.4, (n, n)) \
        & ~jnp.eye(n, dtype=bool)
    tree = {"a": jax.random.normal(jax.random.PRNGKey(7), (n, 9, 3)),
            "b": jax.random.normal(jax.random.PRNGKey(8), (n, 17))}
    got = ops.mix_masked_pytree(edges, tree, interpret=True)
    want = apply_mixing(uniform_weights_jax(edges), tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]), atol=1e-4)


def test_block_size_invariance():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))
    a = ops.pairwise_cosine(x, block_d=512, interpret=True)
    b = ops.pairwise_cosine(x, block_d=4096, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


if HAS_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 10**6), st.integers(2, 12),
           st.integers(1, 300))
    def test_gram_property(seed, n, d):
        x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
        got = ops.pairwise_cosine(x, interpret=True)
        m = np.asarray(got)
        assert m.shape == (n, n)
        assert (np.abs(m) <= 1 + 1e-4).all()
        np.testing.assert_allclose(m, m.T, atol=1e-5)


def test_pytree_layer_average():
    tree = {"a": jax.random.normal(jax.random.PRNGKey(1), (6, 33, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(2), (6, 17))}
    got = ops.model_pairwise_cosine(tree, interpret=True)
    want = ref.layer_averaged_cosine_ref(tree)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


def test_mix_pytree_matches_apply_mixing():
    from repro.core import apply_mixing
    n = 6
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (n, n)))
    tree = {"a": jax.random.normal(jax.random.PRNGKey(4), (n, 9, 3))}
    got = ops.mix_pytree(w, tree, interpret=True)
    want = apply_mixing(w, tree)
    np.testing.assert_allclose(np.asarray(got["a"]),
                               np.asarray(want["a"]), atol=1e-4)


# ---------------------------------------------------------------------------
# selective_scan (fused Mamba S6) vs direct recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bt,L,di,ds,blk", [
    (2, 16, 64, 8, 32), (1, 32, 128, 16, 128), (3, 8, 96, 4, 32),
    (2, 64, 256, 16, 64),
])
def test_selective_scan_sweep(bt, L, di, ds, blk):
    from repro.kernels.selective_scan import selective_scan
    ks = jax.random.split(jax.random.PRNGKey(bt * L + di), 6)
    x = jax.random.normal(ks[0], (bt, L, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, L, di)))
    b = jax.random.normal(ks[2], (bt, L, ds)) * 0.5
    c = jax.random.normal(ks[3], (bt, L, ds)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3)
    h0 = jax.random.normal(ks[5], (bt, di, ds)) * 0.1
    y, h = selective_scan(x, dt, b, c, a, h0, di_block=blk,
                          interpret=True)
    yr, hr = ref.selective_scan_ref(x, dt, b, c, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)


def test_selective_scan_chunk_chaining():
    """Two chunks chained through h equal one long chunk."""
    from repro.kernels.selective_scan import selective_scan
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    bt, L, di, ds = 2, 32, 64, 8
    x = jax.random.normal(ks[0], (bt, L, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, L, di)))
    b = jax.random.normal(ks[2], (bt, L, ds)) * 0.5
    c = jax.random.normal(ks[3], (bt, L, ds)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3)
    h0 = jnp.zeros((bt, di, ds))
    y_full, h_full = selective_scan(x, dt, b, c, a, h0, di_block=64,
                                    interpret=True)
    half = L // 2
    y1, h1 = selective_scan(x[:, :half], dt[:, :half], b[:, :half],
                            c[:, :half], a, h0, di_block=64,
                            interpret=True)
    y2, h2 = selective_scan(x[:, half:], dt[:, half:], b[:, half:],
                            c[:, half:], a, h1, di_block=64,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=1e-5)
