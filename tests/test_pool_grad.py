"""The CNN max-pool's gradient: bitwise ``reduce_window``'s, without
``select_and_scatter``.

``repro.models.cnn._pool`` keeps ``lax.reduce_window(max)`` as its
forward and routes the cotangent to each window's first maximum by a
stored int8 window choice.  Here:

* parity: forward and VJP bitwise equal to ``lax.reduce_window``'s and
  ``jax.vjp``'s, for f32 and bf16, at the GN-LeNet pool shapes and an odd
  7x9, on inputs full of ties, all-equal windows, signed zeros and
  ``-inf``, with and without a ``vmap`` over a node axis; a last-max tie
  rule planted in its place fails the same check;
* engagement: the lowered CNN superstep and the benchmark's GN-LeNet
  local step hold no ``select_and_scatter``; the evaluator still pools
  with ``reduce_window``.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InGraphEpidemicStrategy
from repro.data import (DeviceDataStream, dirichlet_partition,
                        make_image_classification, train_test_split)
from repro.dlrt import DecentralizedRunner, RunnerConfig
from repro.dlrt.runtime import make_local_step
from repro.models import cnn
from repro.optim import sgd

ROOT = Path(__file__).resolve().parents[1]
SHAPES = [(32, 32, 32), (16, 16, 32), (7, 9, 5)]
KINDS = ["ties", "equal_windows", "signed_zeros", "neg_inf"]
DTYPES = [jnp.float32, jnp.bfloat16]


def _reference_pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _input(kind, shape, dtype, rng):
    """``[nodes=3, b=2, H, W, C]`` inputs whose windows tie often."""
    full = (3, 2) + shape
    if kind == "ties":
        x = rng.integers(-2, 3, full).astype(np.float32)
    elif kind == "equal_windows":
        h, w, c = shape
        per = rng.integers(-3, 4, (3, 2, (h + 1) // 2, (w + 1) // 2, c))
        x = per.repeat(2, axis=2).repeat(2, axis=3)[:, :, :h, :w]
        x = x.astype(np.float32)
    elif kind == "signed_zeros":
        x = np.where(rng.random(full) < 0.5, -0.0, 0.0).astype(np.float32)
        x[rng.random(full) < 0.1] = -1.0
    else:
        x = rng.integers(-1, 2, full).astype(np.float32)
        x[rng.random(full) < 0.6] = -np.inf
    return jnp.asarray(x, dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _assert_same_pool(x, g, vmapped):
    """Forward and VJP of ``cnn._pool`` bitwise those of
    ``reduce_window``, on ``x [nodes, b, H, W, C]``; node by node
    unless ``vmapped``."""
    def both(pool):
        def one(xi, gi):
            y, vjp = jax.vjp(pool, xi)
            return y, vjp(gi)[0]
        if vmapped:
            return jax.vmap(one)(x, g)
        outs = [one(xi, gi) for xi, gi in zip(x, g)]
        return tuple(jnp.stack(o) for o in zip(*outs))

    (y, dx), (y_ref, dx_ref) = both(cnn._pool), both(_reference_pool)
    np.testing.assert_array_equal(_bits(y), _bits(y_ref))
    np.testing.assert_array_equal(_bits(dx), _bits(dx_ref))


@pytest.mark.parametrize("vmapped", [False, True], ids=["loop", "vmap"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_pool_vjp_bitwise_reduce_window(dtype, shape, kind, vmapped):
    rng = np.random.default_rng(10 * SHAPES.index(shape) + KINDS.index(kind))
    x = _input(kind, shape, dtype, rng)
    h, w, c = shape
    g = jnp.asarray(rng.normal(size=(3, 2, h // 2, w // 2, c)), dtype)
    _assert_same_pool(x, g, vmapped)


def test_last_max_tie_rule_fails_parity(monkeypatch):
    """A planted last-max choice (a later element wins ties) must fail
    the parity check on tied inputs."""
    def last_max_choice(x):
        b, h, w, c = x.shape
        xr = x[:, :h // 2 * 2, :w // 2 * 2].reshape(b, h // 2, 2, w // 2, 2,
                                                   c)
        best = xr[:, :, 0, :, 0]
        choice = jnp.zeros(best.shape, jnp.int8)
        for slot, (i, j) in enumerate(((0, 1), (1, 0), (1, 1)), 1):
            take = xr[:, :, i, :, j] >= best
            best = jnp.where(take, xr[:, :, i, :, j], best)
            choice = jnp.where(take, jnp.int8(slot), choice)
        return choice

    rng = np.random.default_rng(0)
    x = _input("ties", (7, 9, 5), jnp.float32, rng)
    g = jnp.asarray(rng.normal(size=(3, 2, 3, 4, 5)), jnp.float32)
    _assert_same_pool(x, g, vmapped=True)
    monkeypatch.setattr(cnn, "_window_choice", last_max_choice)
    with pytest.raises(AssertionError):
        _assert_same_pool(x, g, vmapped=True)


def _cnn_engine():
    ds = make_image_classification(120, num_classes=4, image_size=8,
                                   seed=0)
    tr, te = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, 4, 0.5, np.random.default_rng(0))
    runner = DecentralizedRunner(
        init_fn=lambda k: cnn.cnn_params(k, num_classes=4, image_size=8,
                                         width=4),
        loss_fn=cnn.cnn_loss, eval_fn=cnn.cnn_loss, optimizer=sgd(0.05),
        batcher=DeviceDataStream(tr, parts, 4, seed=0),
        test_batch={"images": te.images[:16], "labels": te.labels[:16]},
        strategy=InGraphEpidemicStrategy(n=4, k=2, seed=0),
        cfg=RunnerConfig(n_nodes=4, rounds=4, eval_every=2, compiled=True))
    return runner._make_engine()


def test_cnn_superstep_has_no_select_and_scatter():
    engine = _cnn_engine()
    text = engine.lower().as_text()
    assert text.count("select_and_scatter") == 0
    assert text.count("reduce_window") == 2   # the two pools' forwards
    evaluator = engine._evaluate.lower(engine.params,
                                       engine.test_batch).as_text()
    assert evaluator.count("reduce_window") == 2
    assert evaluator.count("select_and_scatter") == 0


def test_gn_lenet_local_step_has_no_select_and_scatter():
    """The benchmark's GN-LeNet (three pools), built from the program's
    layer functions, at its published widths and a node axis of 2."""
    sys.path.insert(0, str(ROOT))
    from chipbench import model as node_model, reference

    cfg = json.loads(
        (ROOT / "chipbench/configs/gn-lenet-cifar10.json").read_text())
    arch = reference.arch(cfg)
    params = jax.eval_shape(jax.vmap(lambda k: reference.init_node(k, arch)),
                            jax.random.split(jax.random.key(0), 2))
    opt = jax.eval_shape(jax.vmap(sgd(0.05).init), params)
    batch = {"images": jax.ShapeDtypeStruct((2, 8, 32, 32, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    step = make_local_step(node_model.loss_fn(arch), sgd(0.05))
    text = jax.jit(step).lower(params, opt, batch).as_text()
    assert text.count("select_and_scatter") == 0
    assert text.count("reduce_window") == 3
