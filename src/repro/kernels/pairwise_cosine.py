"""Pallas TPU kernel: blocked pairwise Gram matrix -> cosine similarity.

The Morph hot spot (Eq. 3): for one layer's node-stacked parameters
``X [n, D]`` (D up to hundreds of millions), compute the ``[n, n]`` matrix
of pairwise cosine similarities.  The dominant op is the Gram matrix
``X @ X^T``, an MXU matmul — but D far exceeds VMEM, so we tile:

  grid = (D // block_d,)   sequential on TPU
  step i loads ``X[:, i*block_d:(i+1)*block_d]`` into VMEM ([n, block_d],
  lane-aligned), accumulates ``x_blk @ x_blk^T`` into the [n, n] f32
  output block (constant index map -> stays resident in VMEM across the
  whole grid — the standard TPU reduction pattern).

Row norms are the Gram diagonal, so normalization is a free epilogue in
the wrapper (``ops.pairwise_cosine``).  VMEM budget per step:
``n * block_d * 4B`` (e.g. 128 x 65536 x 4 = 32 MB > VMEM -> default
block_d 8192 = 4 MB, double-buffered 8 MB: fits comfortably).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 8192


def _gram_kernel(x_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def gram_matrix(x: jax.Array, *, block_d: int = DEFAULT_BLOCK_D,
                interpret: bool = False) -> jax.Array:
    """``X [n, D] -> X @ X^T [n, n]`` in f32, D-blocked in VMEM.

    D must be a multiple of ``block_d`` (the wrapper pads).
    """
    n, d = x.shape
    if d % block_d != 0:
        raise ValueError(f"D={d} not a multiple of block_d={block_d}")
    grid = (d // block_d,)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(x)
