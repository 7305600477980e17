"""The trip-count-aware HLO cost model vs analytic ground truth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloCostModel, analyse_hlo


def _cost(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    return analyse_hlo(compiled.as_text())


def test_plain_matmul():
    s = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    res = _cost(lambda x, y: x @ y, s, w)
    assert res["flops"] == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


def test_scan_multiplies_trip_count():
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    res = _cost(f, s)
    assert res["flops"] == pytest.approx(7 * 2 * 128**3, rel=0.01)


def test_nested_scans_multiply():
    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x):
        def inner(c, _):
            return jnp.tanh(c @ c), None
        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    res = _cost(f, s)
    analytic = 12 * (2 * 64**3 + 64 * 64)
    assert res["flops"] == pytest.approx(analytic, rel=0.02)
    assert res["unknown_trip_whiles"] == 0


def test_op_counts_trip_weighted():
    """The executed-op tally multiplies by scan trip counts and sums
    into op_count_total — the perf CI gate's op-count metric."""
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    res = _cost(f, s)
    assert res["op_count_total"] == pytest.approx(
        sum(res["op_counts"].values()))
    # the body's dot executes 7 times (it may appear as "dot" or be
    # wrapped in a counted fusion — either way >= 7 body ops show up)
    body_ops = res["op_count_total"] - res["op_counts"].get("while", 0)
    assert body_ops >= 7


def test_bytes_positive_and_bounded_below_by_io():
    s = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    res = _cost(lambda x: x + 1.0, s)
    assert res["bytes"] >= 2 * 1024 * 1024 * 4   # read + write


def test_collectives_counted_with_trip_multiplier():
    """An all-reduce inside a scan counts once per iteration."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:1]).reshape(1)
    mesh = Mesh(devs, ("x",))

    def f(x):
        def body(c, _):
            s = jax.lax.psum(c, "x")
            return c * 0.5 + s * 0.01, None   # keep carry device-varying
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    g = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    compiled = jax.jit(g).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile()
    res = analyse_hlo(compiled.as_text())
    counts = res["collective_counts"]
    if counts:                                # single-device may elide
        assert sum(counts.values()) >= 5


def test_parser_handles_real_module():
    """Parse a realistically-sized compiled module end to end."""
    import repro.configs as C
    from repro.models import model
    cfg = C.get_config("llama3.2-3b").reduced()
    params = jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((1, 32), jnp.int32)}
    compiled = jax.jit(
        lambda p, b: model.loss_fn(p, b, cfg)[0]).lower(
            params, batch).compile()
    res = analyse_hlo(compiled.as_text())
    assert res["flops"] > 1e6                # a real model's worth
    assert res["bytes"] > 1e5
