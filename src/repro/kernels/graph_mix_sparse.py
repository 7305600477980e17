"""Pallas TPU kernel: block-sparse graph mixing from CSR adjacency.

The dense ``graph_mix`` contracts a row-stochastic ``[n, n]`` W against
``X [n, D]`` — O(n²·D) MXU flops even when only k ≪ n entries per row
are nonzero.  This kernel does the O(n·k·D) version straight from the
CSR slots: **gather tiles, then MAC**.

``X`` is viewed as ``[n, D / (s·128), s, 128]`` (``s`` = the sublane
count of its dtype, 8 for f32 and 16 for bf16), so one ``(s, 128)``
hardware tile holds ``s·128`` consecutive features of a single node.
Per grid step ``(i, j)`` — receiver block i, D-block j — the kernel:

1. reads the block's ``[block_n, k + 1]`` sender indices and weights
   from SMEM (scalar memory, so the values can drive copies); slot k is
   each receiver's own row with weight ``w_self``;
2. DMAs those rows' whole tiles of D-block j from the HBM-resident
   ``X`` into a VMEM scratch buffer.  Each copy slices only the node
   and tile axes, never inside a tile, which is what the chip's DMA
   engine requires (``X`` is never tiled through VMEM wholesale: only
   the gathered rows move);
3. sums the weighted slots per receiver on the VPU in f32 and writes
   the ``[block_n, block_d]`` output tile.

On TPU it compiles to Mosaic; ``interpret=True`` executes this body on
CPU for the parity tests.  There is no other fallback: the XLA sparse
path is ``repro.sparse.mix``, which the engine selects with
``use_pallas=False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def sublanes(dtype) -> int:
    """Rows of one ``(sublanes, 128)`` TPU tile for ``dtype``."""
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


def _make_kernel(bn: int, slots: int, bt: int):
    def kernel(idx_ref, w_ref, x_hbm, o_ref, scratch, sem):
        j = pl.program_id(1)

        def copy(s):
            row = idx_ref[s // slots, s % slots]
            return pltpu.make_async_copy(
                x_hbm.at[row, pl.ds(j * bt, bt)], scratch.at[s], sem)

        def start(s, carry):
            copy(s).start()
            return carry

        def wait(s, carry):
            copy(s).wait()
            return carry

        jax.lax.fori_loop(0, bn * slots, start, 0)
        jax.lax.fori_loop(0, bn * slots, wait, 0)
        for r in range(bn):
            acc = w_ref[r, 0] * scratch[r * slots].astype(jnp.float32)
            for s in range(1, slots):
                acc = acc + w_ref[r, s] * scratch[r * slots + s].astype(
                    jnp.float32)
            o_ref[r] = acc.astype(o_ref.dtype)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_d", "interpret"))
def graph_mix_sparse(idx: jax.Array, w: jax.Array, w_self: jax.Array,
                     x: jax.Array, *, block_n: int, block_d: int,
                     interpret: bool = False) -> jax.Array:
    """CSR mix: ``out[i] = w_self[i] · x[i] + Σ_s w[i, s] · x[idx[i, s]]``.

    Shapes (pre-padded by ``ops.mix_sparse``): ``idx``/``w`` are
    ``[n, k]`` (int32 / f32, invalid slots = own row with weight 0),
    ``w_self`` is ``[n]`` f32, ``X`` is ``[n, D]`` with ``n`` a multiple
    of ``block_n``, ``D`` a multiple of ``block_d``, and ``block_d`` a
    multiple of ``sublanes(X.dtype) · 128``.
    """
    n, k = idx.shape
    nx, d = x.shape
    sl = sublanes(x.dtype)
    if n != nx:
        raise ValueError(f"idx rows ({n}) must match X rows ({nx})")
    if n % block_n != 0:
        raise ValueError(f"n={n} not a multiple of block_n={block_n}")
    if block_d % (sl * LANES) != 0:
        raise ValueError(f"block_d={block_d} not a multiple of the "
                         f"{sl}x{LANES} tile")
    if d % block_d != 0:
        raise ValueError(f"D={d} not a multiple of block_d={block_d}")
    own = jnp.arange(n, dtype=jnp.int32)[:, None]
    idx_all = jnp.concatenate([idx.astype(jnp.int32), own], axis=1)
    w_all = jnp.concatenate([w.astype(jnp.float32),
                             w_self.astype(jnp.float32)[:, None]], axis=1)
    bt = block_d // (sl * LANES)
    tiles = x.reshape(n, d // (sl * LANES), sl, LANES)
    out = pl.pallas_call(
        _make_kernel(block_n, k + 1, bt),
        grid=(n // block_n, d // block_d),
        in_specs=[
            pl.BlockSpec((block_n, k + 1), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, k + 1), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_n, bt, sl, LANES),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_n * (k + 1), bt, sl, LANES), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(idx_all, w_all, tiles)
    return out.reshape(n, d)
