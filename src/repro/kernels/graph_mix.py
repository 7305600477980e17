"""Pallas TPU kernel: node-axis graph mixing ``Y = W @ X``, D-blocked.

Alg. 2 line 12 for all nodes at once: the row-stochastic mixing matrix
``W [n, n]`` hits the node-stacked flattened parameters ``X [n, D]``.
W is tiny and stays VMEM-resident (constant index map); X streams
through in ``[n, block_d]`` tiles; each grid step is one MXU matmul.

``graph_mix_masked`` is the fused variant: it takes the raw boolean
in-edge matrix, builds ``W = (E + I) / row_sum`` *inside the kernel*
(VPU epilogue, saves materializing W in HBM) — the uniform-averaging
rule of Morph / Epidemic Learning.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 8192


def _mix_kernel(w_ref, x_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        w_ref[...].astype(jnp.float32), x_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def graph_mix(w: jax.Array, x: jax.Array, *,
              block_d: int = DEFAULT_BLOCK_D,
              interpret: bool = False) -> jax.Array:
    """``W [m, n] @ X [n, D] -> [m, D]``; D multiple of block_d.

    ``m == n`` in the single-device engine; under the sharded superstep
    each device mixes only its own row block, so ``m = n / num_devices``
    (``W`` is the device's row slice of the padded mixing matrix).
    """
    m, n = w.shape
    nx, d = x.shape
    if n != nx:
        raise ValueError(f"W columns ({n}) must match X rows ({nx})")
    if d % block_d != 0:
        raise ValueError(f"D={d} not a multiple of block_d={block_d}")
    return pl.pallas_call(
        _mix_kernel,
        grid=(d // block_d,),
        in_specs=[pl.BlockSpec((m, n), lambda i: (0, 0)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=interpret,
    )(w, x)


def _masked_kernel(e_ref, x_ref, out_ref):
    e = e_ref[...].astype(jnp.float32)
    n = e.shape[0]
    w = e + jnp.eye(n, dtype=jnp.float32)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    out_ref[...] = jax.lax.dot_general(
        w, x_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def graph_mix_masked(edges: jax.Array, x: jax.Array, *,
                     block_d: int = DEFAULT_BLOCK_D,
                     interpret: bool = False) -> jax.Array:
    """Fused uniform-averaging mix from the raw in-edge matrix.

    ``edges [n, n]`` (int/bool; edges[i, j]=1 <=> j sends to i),
    ``X [n, D]``.  Equivalent to ``uniform_weights(edges) @ X``.
    """
    n, d = x.shape
    if d % block_d != 0:
        raise ValueError(f"D={d} not a multiple of block_d={block_d}")
    return pl.pallas_call(
        _masked_kernel,
        grid=(d // block_d,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(edges.astype(jnp.int32), x)
