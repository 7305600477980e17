"""The Pallas kernels compile for a TPU v5e chip, without the chip.

Interpret mode runs a kernel body on the CPU but never shows it to the
chip's compiler, which refuses things interpret mode accepts (a DMA
slice not aligned to the tiling, more VMEM than a kernel may use).
These tests compile each kernel of the engine's main path for a
described ``v5e:2x2`` topology at the widths of the paper's GN-LeNet
(n = 104 = 100 nodes padded to the sublane tile, D = 57,344 = the
51,200-element conv2 leaf padded to the 8,192 block) and of the sparse
population (n = 10,000, k = 8), and check that Mosaic emitted the
kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so that
every test worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.graph_mix import graph_mix, graph_mix_masked
from repro.kernels.graph_mix_sparse import graph_mix_sparse, sublanes
from repro.kernels.pairwise_cosine import gram_matrix

N, D, BLOCK_D = 104, 57_344, 8_192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                    # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_gram_matrix_compiles_for_v5e(one_chip, dtype):
    text = _compiled_text(
        lambda x: gram_matrix(x, block_d=BLOCK_D), one_chip, ((N, D), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_graph_mix_compiles_for_v5e(one_chip, dtype):
    text = _compiled_text(
        lambda w, x: graph_mix(w, x, block_d=BLOCK_D), one_chip,
        ((N, N), jnp.float32), ((N, D), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_graph_mix_masked_compiles_for_v5e(one_chip, dtype):
    text = _compiled_text(
        lambda e, x: graph_mix_masked(e, x, block_d=BLOCK_D), one_chip,
        ((N, N), jnp.int32), ((N, D), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,k,dtype", [(N, 3, jnp.float32),
                                       (112, 3, jnp.bfloat16),
                                       (10_000, 8, jnp.float32)],
                         ids=["n104-k3-f32", "n112-k3-bf16",
                              "n10000-k8-f32"])
def test_graph_mix_sparse_compiles_for_v5e(one_chip, n, k, dtype):
    text = _compiled_text(
        lambda i, w, ws, x: graph_mix_sparse(
            i, w, ws, x, block_n=sublanes(dtype), block_d=BLOCK_D),
        one_chip, ((n, k), jnp.int32), ((n, k), jnp.float32),
        ((n,), jnp.float32), ((n, D), dtype))
    assert "tpu_custom_call" in text
