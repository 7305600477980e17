"""jit'd wrappers around the Pallas kernels.

Handle padding to block multiples, dtype policy, pytree flattening and
the Eq.-3 layer averaging.  On TPU every call compiles to Mosaic; on any
other backend pass ``interpret=True`` to execute the kernel bodies
there (the CPU tests do).  Nothing here falls back to another
implementation.

Padding policy (the shapes the compiled superstep engine actually feeds):

* **D** is padded with zero columns up to a multiple of ``block_d`` —
  zero columns contribute nothing to Gram/mix contractions;
* **n** is padded with zero rows up to a multiple of the sublane tile
  (8 for f32, 16 for bf16) so the ``[n, n]`` / ``[n, block_d]`` blocks
  are Mosaic-tileable for any population size.  Padded rows produce
  garbage rows in the output, which the wrappers slice away before
  returning — callers always see exact ``[n, n]`` / ``[n, D]`` results.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .graph_mix import (DEFAULT_BLOCK_D, graph_mix, graph_mix_masked)
from .graph_mix_sparse import LANES, graph_mix_sparse, sublanes
from .pairwise_cosine import gram_matrix

_EPS = 1e-12


def _pad_d(x: jax.Array, block_d: int) -> jax.Array:
    d = x.shape[-1]
    rem = d % block_d
    if rem == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, block_d - rem)))


def _pad_n(x: jax.Array, mult: int, axes=(0,)) -> jax.Array:
    """Zero-pad the node axis (or axes) of ``x`` up to a multiple of
    ``mult``."""
    n = x.shape[0]
    rem = n % mult
    if rem == 0:
        return x
    width = [(0, mult - rem) if a in axes else (0, 0)
             for a in range(x.ndim)]
    return jnp.pad(x, width)


def pick_block_d(d: int, block_d: Optional[int] = None) -> int:
    """Effective D-block size for feature dimension ``d``: the explicit
    override when given, else the library default clamped into
    ``[128, DEFAULT_BLOCK_D]``.  Public so the autotuner
    (``repro.tune``) can enumerate candidates around — and record — the
    value a ``block_d=None`` knob actually resolves to."""
    if block_d is not None:
        return block_d
    return min(DEFAULT_BLOCK_D, max(128, d))


_pick_block = pick_block_d          # internal alias (call sites below)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def pairwise_cosine(x: jax.Array, *, block_d: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Cosine similarity between all rows of ``X [n, D]`` -> [n, n]."""
    n = x.shape[0]
    bd = _pick_block(x.shape[-1], block_d)
    xp = _pad_n(_pad_d(x, bd), sublanes(x.dtype))
    g = gram_matrix(xp, block_d=bd, interpret=interpret)[:n, :n]
    norms = jnp.maximum(jnp.sqrt(jnp.diag(g)), _EPS)
    return g / (norms[:, None] * norms[None, :])


def model_pairwise_cosine(stacked_params, *, block_d: Optional[int] = None,
                          interpret: bool = False) -> jax.Array:
    """Eq. 3 on a node-stacked pytree: per-leaf cosine, averaged.

    Drop-in ``sim_fn`` for :func:`repro.core.morph.update_topology`.
    """
    leaves = jax.tree_util.tree_leaves(stacked_params)
    n = leaves[0].shape[0]
    acc = jnp.zeros((n, n), jnp.float32)
    for leaf in leaves:
        acc += pairwise_cosine(leaf.reshape(n, -1), block_d=block_d,
                               interpret=interpret)
    return acc / len(leaves)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mix(w: jax.Array, x: jax.Array, *, block_d: Optional[int] = None,
        interpret: bool = False) -> jax.Array:
    """``W [m, n] @ X [n, D] -> [m, D]`` with D-blocking.

    Pads/unpads both node axes and D transparently.  ``W`` may be
    rectangular: the sharded superstep passes each device's row block
    ``[n_local, n_pad]``, so padding is applied per shard — ``m`` and
    ``n`` are tiled up to the sublane multiple independently and the
    result is sliced back to exact ``[m, d]``.
    """
    m = w.shape[0]
    n, d = x.shape
    bd = _pick_block(d, block_d)
    sl = sublanes(x.dtype)
    wp = jnp.pad(w, ((0, -m % sl), (0, -n % sl)))
    xp = _pad_n(_pad_d(x, bd), sl)
    y = graph_mix(wp, xp, block_d=bd, interpret=interpret)
    return y[:m, :d]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mix_masked(edges: jax.Array, x: jax.Array, *,
               block_d: Optional[int] = None,
               interpret: bool = False) -> jax.Array:
    """Fused uniform-average mixing from the raw in-edge matrix."""
    n, d = x.shape
    bd = _pick_block(d, block_d)
    sl = sublanes(x.dtype)
    ep = _pad_n(edges, sl, axes=(0, 1))
    xp = _pad_n(_pad_d(x, bd), sl)
    y = graph_mix_masked(ep, xp, block_d=bd, interpret=interpret)
    return y[:n, :d]


@functools.partial(jax.jit, static_argnames=("block_d", "block_n",
                                             "interpret"))
def mix_sparse(idx: jax.Array, w: jax.Array, w_self: jax.Array,
               x: jax.Array, *, mask: Optional[jax.Array] = None,
               block_d: Optional[int] = None,
               block_n: Optional[int] = None,
               interpret: bool = False) -> jax.Array:
    """CSR k-sparse mix ``out[i] = w_self[i]·x[i] + Σ_s w[i,s]·x[idx[i,s]]``
    — O(n·k·D) instead of the dense ``mix``'s O(n²·D).

    Runs the block-sparse Pallas kernel
    (:func:`repro.kernels.graph_mix_sparse.graph_mix_sparse`): compiled
    on TPU, or its body on CPU when ``interpret=True`` asks for it; on
    any other backend it raises.  The XLA gather path is
    :func:`repro.sparse.mix.sparse_mix_rows`.  ``block_d`` is rounded
    up to whole ``(sublanes, 128)`` tiles of ``x.dtype``.  ``mask=None``
    trusts ``idx``/``w`` to carry invalid slots as own-row/zero-weight
    already (the :class:`repro.sparse.SparseAdjacency` invariant).
    """
    n, d = x.shape
    if not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            f"the sparse Pallas kernel compiles only for TPU (backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run its "
            "body here, or mix through repro.sparse.mix")
    if mask is not None:
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        idx = jnp.where(mask, idx, rows)
        w = jnp.where(mask, w, 0.0)
    tile = sublanes(x.dtype) * LANES
    bd = -(-_pick_block(d, block_d) // tile) * tile
    bn = block_n or sublanes(x.dtype)
    pad = -n % bn
    if pad:
        tail = jnp.arange(n, n + pad, dtype=jnp.int32)
        idx = jnp.concatenate(
            [idx, jnp.broadcast_to(tail[:, None], (pad, idx.shape[1]))])
        w = _pad_n(w, bn)
        w_self = jnp.pad(w_self, (0, pad))
    xp = _pad_n(_pad_d(x, bd), bn)
    y = graph_mix_sparse(idx, w, w_self, xp, block_n=bn, block_d=bd,
                         interpret=interpret)
    return y[:n, :d]


def mix_sparse_pytree(idx: jax.Array, w: jax.Array, w_self: jax.Array,
                      stacked_params, *, mask: Optional[jax.Array] = None,
                      block_d: Optional[int] = None,
                      interpret: bool = False):
    """Apply the CSR mix leaf-wise over a node-stacked pytree — the
    compiled sparse engine's Pallas mixing path."""
    def one(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        return mix_sparse(idx, w, w_self, flat, mask=mask,
                          block_d=block_d, interpret=interpret).reshape(
            leaf.shape).astype(leaf.dtype)
    return jax.tree_util.tree_map(one, stacked_params)


def mix_pytree(w: jax.Array, stacked_params, *,
               block_d: Optional[int] = None, interpret: bool = False):
    """Apply ``W [m, n]`` to every leaf of a node-stacked pytree
    (``[n, ...]`` -> ``[m, ...]``) via the kernel.  ``m < n`` is the
    sharded-superstep case: ``w`` is one device's row block and the
    leaves are the all-gathered full population."""
    m = w.shape[0]
    def one(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        return mix(w, flat, block_d=block_d, interpret=interpret).reshape(
            (m,) + leaf.shape[1:]).astype(leaf.dtype)
    return jax.tree_util.tree_map(one, stacked_params)


def mix_masked_pytree(edges: jax.Array, stacked_params, *,
                      block_d: Optional[int] = None,
                      interpret: bool = False):
    """Fused uniform-average mixing over a node-stacked pytree — the
    compiled superstep's Pallas mixing path for uniform strategies."""
    def one(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        return mix_masked(edges, flat, block_d=block_d,
                          interpret=interpret).reshape(
            leaf.shape).astype(leaf.dtype)
    return jax.tree_util.tree_map(one, stacked_params)
