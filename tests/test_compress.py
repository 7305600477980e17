"""The compression test tier (DESIGN.md §13).

Pins the codec contract `repro.compress` is built on:

* **Quantizer round-trip** — per-coordinate absolute error is bounded
  by half the quantization step (int8: ``scale / 2``; fp8-e4m3: one
  part in 2^3 of the coordinate plus the subnormal step).  Relative
  error is *not* bounded (a coordinate rounding to 0 has 100% relative
  error) — absolute bounds are the right invariant.
* **Error-feedback exactness** — with payload ``b = params + resid``
  and decoded ``d``, both ``b - d`` and ``d + (b - d)`` are bitwise
  exact in f32 (Sterbenz lemma for the quantizers, disjoint supports
  for top-k).  This makes the telescoping claim — the sum of decoded
  payloads equals the sum of true payloads up to the final residual —
  an exact identity, pinned here over multi-round simulations.
* **Top-k** — idempotence (a k-sparse payload re-encodes to itself),
  k-sparsity, and transmitted-verbatim values.
* **Shape/dtype invariants** — bf16 and f32 leaves, odd feature
  counts, row counts not divisible by 8, int16 -> int32 index fallback
  above ``INT16_MAX_D``.

Property tests run under hypothesis when installed (the CI ``[test]``
extra ships it) and skip individually otherwise (`tests/_hyp.py`);
every property also has a deterministic twin over adversarial values so
the contract stays pinned in minimal environments.
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hyp import given, settings, st

from repro.compress import (DEFAULT_TOPK_FRAC, FP8_MAX, INT8_MAX,
                            CompressConfig, decode_leaf,
                            encode_delta_payload, encode_leaf,
                            encode_payload, leaf_wire_bytes,
                            roundtrip_leaf, topk_k, wire_bytes_tree,
                            zero_residual)
from repro.compress.codec import INT16_MAX_D

INT8 = CompressConfig(quant="int8")
FP8 = CompressConfig(quant="fp8")
TOPK = CompressConfig(topk_frac=0.25)
INT8_TOPK = CompressConfig(quant="int8", topk_frac=0.25)
ALL_CODECS = [INT8, FP8, TOPK, INT8_TOPK]

# Adversarial rows for the deterministic twins: zeros, signed zeros,
# near-normal-min magnitudes, huge magnitudes, bf16-representable
# values.  Subnormals are deliberately absent: XLA CPU/TPU flush them
# to zero, so the exactness contract holds over the *normal* f32 range
# (which is also where the engines are self-consistent — every payload
# flows through the same flushing backend).
ADVERSARIAL = np.array([
    [0.0, -0.0, 0.0, 0.0, 0.0],
    [1.5e-38, -1.5e-38, 1e-20, -1e-20, 2e-38],
    [1e38, -1e38, 3e37, 65504.0, -1.0],
    [1.0, 1.0, 1.0, 1.0, 1.0],
    [127.0, -127.0, 63.5, 0.25, -0.25],
    [math.pi, -math.e, 1 / 3, 2 / 3, -1 / 7],
], np.float32)


def _rand(rows, d, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, d)) * scale).astype(np.float32)


# ---------------------------------------------------------------------------
# Quantizer round-trip bounds.
# ---------------------------------------------------------------------------

def _assert_quant_bound(x, cfg):
    x = np.asarray(x, np.float32)
    d = np.asarray(roundtrip_leaf(jnp.asarray(x), cfg))
    scale = np.max(np.abs(x), axis=1, keepdims=True) / \
        (INT8_MAX if cfg.quant == "int8" else FP8_MAX)
    err = np.abs(x.astype(np.float64) - d.astype(np.float64))
    if cfg.quant == "int8":
        # round-to-nearest: half a step, plus f32 rounding of q * scale.
        bound = scale * 0.5 * (1 + 1e-5) + 1e-30
    else:
        # e4m3: ulp/2 <= |v| / 2^4 within a binade, plus the subnormal
        # step (2^-9 in code units -> scale * 2^-10 after halving).
        bound = np.abs(x) / 16.0 + scale * 2.0 ** -10 + 1e-30
    assert (err <= bound).all(), \
        f"max excess {np.max(err - bound):g} for {cfg.spec()}"


@pytest.mark.parametrize("cfg", [INT8, FP8], ids=lambda c: c.spec())
def test_quantizer_roundtrip_error_bounded(cfg):
    for seed, scale in [(0, 1.0), (1, 1e-6), (2, 1e6)]:
        _assert_quant_bound(_rand(7, 33, seed, scale), cfg)
    _assert_quant_bound(ADVERSARIAL, cfg)


@pytest.mark.parametrize("cfg", [INT8, FP8], ids=lambda c: c.spec())
def test_zero_rows_decode_exactly_zero(cfg):
    x = np.zeros((3, 9), np.float32)
    d = np.asarray(roundtrip_leaf(jnp.asarray(x), cfg))
    assert (d == 0.0).all()


# Generated coordinates stay in the normal f32 range (or exactly 0):
# XLA flushes subnormals, so sub-1e-20 magnitudes test the backend's
# flush behaviour rather than the codec contract.  The bounds are the
# f32 values nearest 1e-20 / 1e30: hypothesis rejects width=32 bounds
# that f32 cannot represent exactly.
_TINY, _HUGE = float(np.float32(1e-20)), float(np.float32(1e30))
FINITE = st.one_of(st.just(0.0),
                   st.floats(min_value=_TINY, max_value=_HUGE, width=32),
                   st.floats(min_value=-_HUGE, max_value=-_TINY, width=32))


@settings(max_examples=50, deadline=None)
@given(st.lists(FINITE, min_size=4, max_size=64))
def test_quantizer_roundtrip_error_bounded_property(vals):
    x = np.asarray(vals, np.float32).reshape(1, -1)
    _assert_quant_bound(x, INT8)
    _assert_quant_bound(x, FP8)


# ---------------------------------------------------------------------------
# Error-feedback exactness (the identity the scan carry relies on).
# ---------------------------------------------------------------------------

def _assert_ef_exact(x, cfg):
    b = jnp.asarray(np.asarray(x, np.float32))
    d = roundtrip_leaf(b, cfg)
    e = b - d
    assert np.array_equal(np.asarray(d + e), np.asarray(b)), \
        f"d + (b - d) != b bitwise for {cfg.spec()}"


@pytest.mark.parametrize("cfg", ALL_CODECS, ids=lambda c: c.spec())
def test_error_feedback_residual_exact(cfg):
    for seed, scale in [(0, 1.0), (3, 1e-8), (4, 1e8)]:
        _assert_ef_exact(_rand(6, 41, seed, scale), cfg)
    _assert_ef_exact(ADVERSARIAL, cfg)


@settings(max_examples=50, deadline=None)
@given(st.lists(FINITE, min_size=4, max_size=64))
def test_error_feedback_residual_exact_property(vals):
    # two rows: drop the last coordinate of an odd-length draw
    x = np.asarray(vals[: len(vals) // 2 * 2], np.float32).reshape(2, -1)
    for cfg in ALL_CODECS:
        _assert_ef_exact(x, cfg)


@pytest.mark.parametrize("cfg", ALL_CODECS, ids=lambda c: c.spec())
def test_error_feedback_telescopes_exactly(cfg):
    """Over T rounds of changing params, each round's payload
    ``b_t = params_t + e_t`` decodes to ``d_t = b_t - e_{t+1}``
    *exactly* in f32, so the decoded stream telescopes against the
    payload stream: ``sum_t d_t = sum_t b_t - sum_{t>=1} e_t`` as an
    identity (each term is an exact f32 value; the sums run in f64,
    where adding a handful of f32 values is itself exact)."""
    tree = {"w": jnp.asarray(_rand(4, 19, seed=7)),
            "b": jnp.asarray(_rand(4, 3, seed=8))}
    resid = zero_residual(tree)
    dec_sum = {k: np.zeros(np.asarray(v).shape, np.float64)
               for k, v in tree.items()}
    pay_sum = {k: np.zeros(np.asarray(v).shape, np.float64)
               for k, v in tree.items()}
    res_sum = {k: np.zeros(np.asarray(v).shape, np.float64)
               for k, v in tree.items()}
    params = tree
    for t in range(6):
        _, dec, new_resid = encode_payload(params, resid, cfg)
        for k in tree:
            # the payload the codec actually saw, recomputed bitwise
            b = np.asarray(jnp.asarray(params[k]).astype(jnp.float32)
                           + jnp.asarray(resid[k]))
            # per-round identity, bitwise: d_t + e_{t+1} == b_t
            np.testing.assert_array_equal(
                np.asarray(dec[k]) + np.asarray(new_resid[k]), b)
            dec_sum[k] += np.asarray(dec[k], np.float64)
            pay_sum[k] += b.astype(np.float64)
            res_sum[k] += np.asarray(new_resid[k], np.float64)
        resid = new_resid
        params = {k: jnp.asarray(np.asarray(v) * 0.9 + 0.01)
                  for k, v in params.items()}
    for k in tree:
        np.testing.assert_array_equal(dec_sum[k], pay_sum[k] - res_sum[k])


def test_error_feedback_off_keeps_residual():
    cfg = CompressConfig(quant="int8", error_feedback=False)
    tree = {"w": jnp.asarray(_rand(3, 8))}
    r0 = {"w": jnp.asarray(_rand(3, 8, seed=5))}
    _, dec, r1 = encode_payload(tree, r0, cfg)
    np.testing.assert_array_equal(np.asarray(r1["w"]), np.asarray(r0["w"]))
    np.testing.assert_array_equal(
        np.asarray(dec["w"]),
        np.asarray(roundtrip_leaf(tree["w"].reshape(3, -1), cfg)))


# ---------------------------------------------------------------------------
# Difference-coded error feedback (the engines' replica hot path).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [INT8, FP8], ids=lambda c: c.spec())
def test_delta_payload_quant_only_matches_direct(cfg):
    """Without top-k every coordinate is transmitted, so the restricted
    residual update degenerates to ``b - dec`` and the delta primitive
    is bitwise the direct one."""
    tree = {"w": jnp.asarray(_rand(4, 23, seed=2))}
    resid = {"w": jnp.asarray(_rand(4, 23, seed=3, scale=1e-3))}
    wa, da, ra = encode_payload(tree, resid, cfg)
    wb, db, rb = encode_delta_payload(tree, resid, cfg)
    for key in wa["w"]:
        np.testing.assert_array_equal(np.asarray(wa["w"][key]),
                                      np.asarray(wb["w"][key]))
    np.testing.assert_array_equal(np.asarray(da["w"]), np.asarray(db["w"]))
    np.testing.assert_array_equal(np.asarray(ra["w"]), np.asarray(rb["w"]))


@pytest.mark.parametrize("cfg", [TOPK, INT8_TOPK], ids=lambda c: c.spec())
def test_delta_payload_dropped_coords_stay_out_of_residual(cfg):
    """Top-k-dropped coordinates must NOT enter the residual (they
    persist in the replica gap); transmitted coordinates carry exactly
    their quantization error, bounded by step/2."""
    x = _rand(5, 24, seed=6)
    tree = {"w": jnp.asarray(x)}
    wire, dec, resid = encode_delta_payload(tree, zero_residual(tree), cfg)
    idx = np.asarray(wire["w"]["idx"], np.int64)
    sent = np.zeros((5, 24), bool)
    sent[np.arange(5)[:, None], idx] = True
    r = np.asarray(resid["w"])
    assert (r[~sent] == 0.0).all()
    d = np.asarray(dec["w"])
    np.testing.assert_array_equal(r[sent], (x - d)[sent])
    if cfg.quant == "int8":
        step = np.max(np.abs(x), axis=1, keepdims=True) / INT8_MAX
        assert (np.abs(r) <= step * 0.5 * (1 + 1e-5)).all()


def test_delta_payload_replica_converges_without_blowup():
    """The regression pinned by the double-counting bug: integrate
    ``hat += decode(encode(params - hat))`` against *constant* params
    under int8+top-k.  The replica gap must shrink monotonically-ish to
    (near) zero and the transmitted payload magnitude must stay bounded
    by the initial gap — with the dropped error double-fed through the
    residual (the direct :func:`encode_payload` applied to deltas), a
    chronically dropped coordinate's payload instead grows linearly
    and the replica overshoots the model."""
    p = jnp.asarray(_rand(3, 40, seed=9))
    tree = {"w": p}
    gap0 = float(jnp.max(jnp.abs(p)))
    hat = {"w": jnp.zeros_like(p)}
    resid = zero_residual(tree)
    gaps = []
    for _ in range(24):
        delta = {"w": tree["w"] - hat["w"]}
        _, dec, resid = encode_delta_payload(delta, resid, INT8_TOPK)
        payload_mag = float(jnp.max(jnp.abs(delta["w"] + 0)))
        assert payload_mag <= gap0 * 1.5 + 1e-6
        hat = {"w": hat["w"] + dec["w"]}
        gaps.append(float(jnp.max(jnp.abs(tree["w"] - hat["w"]))))
    # every coordinate eventually transmitted: gap collapses to the
    # quantization floor (~step/2 of the final, tiny deltas)
    assert gaps[-1] < 0.02 * gap0
    assert gaps[-1] < gaps[0]


# ---------------------------------------------------------------------------
# Top-k structure.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [TOPK, CompressConfig(topk_frac=0.5)],
                         ids=lambda c: c.spec())
def test_topk_idempotent_and_k_sparse(cfg):
    x = jnp.asarray(_rand(5, 24, seed=11))
    k = topk_k(24, cfg.topk_frac)
    once = np.asarray(roundtrip_leaf(x, cfg))
    assert (np.count_nonzero(once, axis=1) <= k).all()
    # kept coordinates are transmitted verbatim
    mask = once != 0
    np.testing.assert_array_equal(once[mask], np.asarray(x)[mask])
    twice = np.asarray(roundtrip_leaf(jnp.asarray(once), cfg))
    np.testing.assert_array_equal(once, twice)


def test_topk_keeps_largest_magnitudes():
    x = jnp.asarray(np.array([[1.0, -8.0, 3.0, 0.5, -6.0, 2.0, 0.1, 7.0]],
                             np.float32))
    cfg = CompressConfig(topk_frac=0.5)          # k = 4 of 8
    d = np.asarray(roundtrip_leaf(x, cfg))[0]
    np.testing.assert_array_equal(
        d, np.array([0.0, -8.0, 0.0, 0.0, -6.0, 0.0, 0.0, 7.0, ],
                    np.float32) + np.array([0, 0, 3.0, 0, 0, 0, 0, 0],
                                           np.float32))


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(min_value=-1e6, max_value=1e6, width=32),
                min_size=8, max_size=40),
       st.floats(min_value=0.1, max_value=1.0))
def test_topk_sparsity_property(vals, frac):
    x = np.asarray(vals, np.float32).reshape(1, -1)
    cfg = CompressConfig(topk_frac=frac)
    k = topk_k(x.shape[1], frac)
    d = np.asarray(roundtrip_leaf(jnp.asarray(x), cfg))
    assert np.count_nonzero(d) <= k


# ---------------------------------------------------------------------------
# Shape / dtype invariants.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", ALL_CODECS, ids=lambda c: c.spec())
@pytest.mark.parametrize("rows,d", [(3, 7), (5, 33), (1, 2), (7, 129)])
def test_wire_shapes_and_dtypes(cfg, rows, d):
    x = jnp.asarray(_rand(rows, d, seed=rows * d))
    wire = encode_leaf(x, cfg)
    k = d if cfg.topk_frac is None else topk_k(d, cfg.topk_frac)
    if cfg.quant != "none":
        assert wire["q"].shape == (rows, k)
        assert wire["q"].dtype == (jnp.int8 if cfg.quant == "int8"
                                   else jnp.float8_e4m3fn)
        assert wire["scale"].shape == (rows,)
        assert wire["scale"].dtype == jnp.float32
    else:
        assert wire["v"].shape == (rows, k)
    if cfg.topk_frac is not None:
        assert wire["idx"].shape == (rows, k)
        assert wire["idx"].dtype == jnp.int16
    dec = decode_leaf(wire, d, cfg)
    assert dec.shape == (rows, d) and dec.dtype == jnp.float32


def test_bf16_leaves_roundtrip_via_f32():
    """The engines feed ``params + resid`` upcast to f32; a bf16 leaf's
    payload is exactly representable, so EF exactness carries over."""
    x = jnp.asarray(_rand(4, 17), jnp.bfloat16)
    tree = {"w": x}
    _, dec, resid = encode_payload(tree, zero_residual(tree), INT8_TOPK)
    assert dec["w"].dtype == jnp.float32
    assert resid["w"].dtype == jnp.float32
    b = np.asarray(x.astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(dec["w"]) + np.asarray(resid["w"]), b)


def test_int32_index_fallback_above_int16_range():
    d = INT16_MAX_D + 5
    x = jnp.asarray(_rand(2, d, seed=1))
    wire = encode_leaf(x, TOPK)
    assert wire["idx"].dtype == jnp.int32
    # index-side accounting: min(explicit index list, packed position
    # bitmap) — the bitmap (d/8, k-independent) wins above frac 1/16
    k = topk_k(d, TOPK.topk_frac)
    assert leaf_wire_bytes(d, TOPK) == k * 4 + -(-d // 8)
    assert leaf_wire_bytes(100, TOPK) == topk_k(100, 0.25) * 4 + 13
    # a genuinely tiny fraction keeps the explicit index list
    assert leaf_wire_bytes(1000, CompressConfig(topk_frac=0.01)) \
        == topk_k(1000, 0.01) * 4 + topk_k(1000, 0.01) * 2


# ---------------------------------------------------------------------------
# Config parsing and wire-byte accounting.
# ---------------------------------------------------------------------------

def test_spec_parse_roundtrip():
    for spec in ("none", "int8", "fp8", "topk0.25", "int8+topk0.25",
                 "fp8+topk0.5", "int8+topk0.25+gamma0.5"):
        assert CompressConfig.parse(spec).spec() == spec
    assert CompressConfig.parse("topk").topk_frac == DEFAULT_TOPK_FRAC
    assert CompressConfig.parse(None) == CompressConfig()
    cfg = CompressConfig(quant="int8")
    assert CompressConfig.parse(cfg) is cfg
    assert not CompressConfig.parse("none").enabled
    assert CompressConfig.parse("int8").enabled


def test_consensus_gamma_resolution():
    # explicit gamma wins; dense codecs default to the full step;
    # top-k damps with the kept fraction (CHOCO-style, min(1, 2*frac))
    assert CompressConfig.parse("int8+gamma0.4").consensus_gamma == 0.4
    assert CompressConfig.parse("int8").consensus_gamma == 1.0
    assert CompressConfig.parse("topk0.5").consensus_gamma == 1.0
    assert CompressConfig.parse("topk0.25").consensus_gamma == 0.5
    assert CompressConfig.parse("topk0.25+gamma1").consensus_gamma == 1.0


def test_spec_parse_rejects():
    with pytest.raises(TypeError, match="auto"):
        CompressConfig.parse("auto")
    with pytest.raises(ValueError, match="unknown compress term"):
        CompressConfig.parse("int7")
    with pytest.raises(ValueError, match="duplicate"):
        CompressConfig.parse("int8+fp8")
    with pytest.raises(ValueError):
        CompressConfig(quant="int4")
    with pytest.raises(ValueError):
        CompressConfig(topk_frac=1.5)
    with pytest.raises(ValueError, match="duplicate gamma"):
        CompressConfig.parse("gamma0.5+gamma0.7")
    with pytest.raises(ValueError, match="gamma"):
        CompressConfig(gamma=0.0)
    with pytest.raises(TypeError):
        CompressConfig.parse(42)


def test_wire_bytes_accounting():
    tree = {"w": jnp.zeros((6, 784, 16)), "b": jnp.zeros((6, 16))}
    dense = wire_bytes_tree(tree, 6, CompressConfig())
    assert dense == 4 * (784 * 16 + 16)
    int8 = wire_bytes_tree(tree, 6, INT8)
    assert int8 == (784 * 16 + 4) + (16 + 4)
    both = wire_bytes_tree(tree, 6, INT8_TOPK)
    k1, k2 = topk_k(784 * 16, 0.25), topk_k(16, 0.25)
    assert both == (k1 + -(-784 * 16 // 8) + 4) + (k2 + 2 + 4)
    assert dense / both > 4.0           # the fig13 acceptance geometry
    # moderate sparsity also clears 4x under the bitmap support pricing
    half = wire_bytes_tree(tree, 6, CompressConfig("int8", 0.5))
    assert dense / half > 4.0


# ---------------------------------------------------------------------------
# Engine integration guards (the knob's failure modes).
# ---------------------------------------------------------------------------

def _tiny_runner(**cfg_kw):
    from repro.core import InGraphMorphStrategy
    from repro.data import (dirichlet_partition, make_image_classification,
                            train_test_split)
    from repro.data.pipeline import StackedBatcher
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.models.tiny import mlp_loss, mlp_params
    from repro.optim import sgd
    rng = np.random.default_rng(0)
    ds = make_image_classification(120, num_classes=3, image_size=6, seed=0)
    tr, te = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, 4, 0.5, rng)
    return DecentralizedRunner(
        init_fn=mlp_params, loss_fn=mlp_loss, eval_fn=mlp_loss,
        optimizer=sgd(0.05),
        batcher=StackedBatcher(tr, parts, 8, seed=3),
        test_batch={"images": te.images, "labels": te.labels},
        strategy=InGraphMorphStrategy(n=4, k=2, view_size=3, seed=0),
        cfg=RunnerConfig(n_nodes=4, rounds=2, eval_every=2, **cfg_kw))


def test_engine_rejects_codec_with_pallas():
    with pytest.raises(ValueError, match="Pallas"):
        _tiny_runner(compiled=True, compress="int8", use_pallas=True,
                     interpret=True).run()


def test_host_loop_rejects_codec():
    with pytest.raises(TypeError, match="compiled"):
        _tiny_runner(compiled=False, compress="int8").run()


def test_engine_rejects_auto_spec_directly():
    from repro.dlrt.compiled import CompiledSuperstep
    with pytest.raises(TypeError, match="auto"):
        CompressConfig.parse("auto")
    with pytest.raises(TypeError):
        CompiledSuperstep(
            init_fn=None, loss_fn=None, eval_fn=None, optimizer=None,
            batcher=None, test_batch={}, strategy=None,
            cfg=None, compress="int8")


def test_disabled_codec_is_none_spec():
    assert CompressConfig.parse("none").spec() == "none"
    assert not CompressConfig(quant="none", topk_frac=None).enabled
