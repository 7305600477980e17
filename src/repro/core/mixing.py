"""Mixing matrices and their application to node-stacked parameter pytrees.

The model-exchange step of every protocol in this repo reduces to one
row-stochastic matrix ``W_t`` applied along the node axis:

    x_i <- sum_j W[i, j] * x_j

* Morph / Epidemic Learning use **uniform averaging** over self + received
  models (Alg. 2 line 12):  ``W[i, j] = 1 / (|S_t^i| + 1)``.
* The Static baseline uses **Metropolis-Hastings** weights on its fixed
  undirected graph, the classical choice that makes W symmetric and doubly
  stochastic, removing topological bias.
* Fully connected uses ``W = 1/n``.

``apply_mixing`` is the JAX path (einsum over the node axis — lowered by
XLA to all-gather/reduce-scatter when the node axis is sharded); the Pallas
kernel ``repro.kernels.graph_mix`` implements the same contraction with
explicit VMEM blocking for the flattened-parameter hot path.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# W builders (host-side, numpy — graphs are tiny).
# ---------------------------------------------------------------------------

def uniform_weights(edges: np.ndarray) -> np.ndarray:
    """Alg. 2 l.12: average own + received models uniformly.

    ``edges[i, j]`` = j sends to i.  Rows are stochastic by construction;
    isolated nodes (no in-edges) keep their own model (W[i,i] = 1).
    """
    n = edges.shape[0]
    w = edges.astype(np.float64) + np.eye(n)
    return w / w.sum(axis=1, keepdims=True)


def metropolis_hastings_weights(adj: np.ndarray) -> np.ndarray:
    """MH weights on an undirected graph: W[i,j] = 1/(1+max(d_i,d_j)),
    diagonal soaks up the remainder.  Symmetric & doubly stochastic."""
    adj = np.asarray(adj, bool)
    if not (adj == adj.T).all():
        raise ValueError("Metropolis-Hastings weights need an undirected "
                         "(symmetric) adjacency matrix")
    n = adj.shape[0]
    deg = adj.sum(axis=1)
    w = np.zeros((n, n), np.float64)
    ii, jj = np.nonzero(adj)
    w[ii, jj] = 1.0 / (1.0 + np.maximum(deg[ii], deg[jj]))
    np.fill_diagonal(w, 0.0)
    np.fill_diagonal(w, 1.0 - w.sum(axis=1))
    return w


def fully_connected_weights(n: int) -> np.ndarray:
    """W = 1/n everywhere — the fully-connected upper bound's mixing."""
    return np.full((n, n), 1.0 / n)


def uniform_weights_jax(edges: jax.Array) -> jax.Array:
    """jit-safe twin of :func:`uniform_weights` for the in-graph controller."""
    n = edges.shape[0]
    w = edges.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32)
    return w / w.sum(axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# Application to stacked pytrees.
# ---------------------------------------------------------------------------

def tensordot_mix_leaf(w: jax.Array, leaf: jax.Array,
                       chunk_d: Optional[int] = None,
                       precision=jax.lax.Precision.HIGHEST,
                       cast_back: bool = True) -> jax.Array:
    """``W [m, n] @ leaf [n, ...]`` over the node axis, one leaf at a time.

    ``chunk_d=None`` is the classic whole-leaf contraction: tensordot
    over the node axis only, no reshape, so sharded trailing dims stay
    sharded.  With ``chunk_d`` set, the flattened feature axis is
    processed ``chunk_d`` elements per step so the f32-upcast operand
    and result buffers stay ``O(n · chunk_d)`` instead of ``O(n ·
    leaf_size)`` — the chunked-per-layer exchange path (DESIGN.md §12).
    Every output element is the *same* length-``n`` dot product either
    way (the contraction axis is never split).  XLA's CPU backend picks
    its dot kernel by operand shape, so chunked and whole-leaf results
    can differ in the last ulp at larger ``n``/``D``.

    ``cast_back=False`` returns the f32 accumulation (the sharded psum
    schedule reduces partial products across devices before the final
    downcast).
    """
    w32 = w.astype(jnp.float32)
    out_dtype = leaf.dtype if cast_back else jnp.float32
    if chunk_d is None:
        mixed = jnp.tensordot(w32, leaf.astype(jnp.float32),
                              axes=((1,), (0,)), precision=precision)
        return mixed.astype(out_dtype)
    m = w.shape[0]
    flat = leaf.reshape(leaf.shape[0], -1)
    d = flat.shape[1]
    pieces = [jnp.tensordot(w32, flat[:, s:s + chunk_d].astype(jnp.float32),
                            axes=((1,), (0,)), precision=precision)
              for s in range(0, max(d, 1), chunk_d)]
    out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)
    return out.reshape((m,) + leaf.shape[1:]).astype(out_dtype)


@partial(jax.jit, static_argnames=("precision", "chunk_d"))
def apply_mixing(w: jax.Array, stacked_params,
                 precision: str = "highest",
                 chunk_d: Optional[int] = None):
    """``x_i <- sum_j W[i,j] x_j`` for every leaf of a node-stacked pytree.

    Leaves have shape ``[n, ...]``.  The contraction runs in f32 and casts
    back to the leaf dtype, so bf16-stored models do not lose the averaging
    precision (matters once n is large).  ``chunk_d`` bounds the f32
    upcast buffers per leaf (:func:`tensordot_mix_leaf`) — the same
    result up to last-ulp dot-kernel rounding; leave ``None`` when
    leaves carry sharded trailing dims (chunking reshapes them).
    """
    prec = jax.lax.Precision(precision.lower()) \
        if isinstance(precision, str) else precision

    return jax.tree_util.tree_map(
        lambda leaf: tensordot_mix_leaf(w, leaf, chunk_d, prec),
        stacked_params)


def apply_consensus_correction(mixed, stacked_params, decoded,
                               gamma: float = 1.0):
    """Consensus-difference form of compressed mixing (DESIGN.md §13):
    given ``mixed_i = sum_j W[i,j] decoded_j`` (the self row contracted
    over its own *decoded* payload like everyone else's),

        ``x_i <- params_i + gamma * (mixed_i - decoded_i)
              =  params_i + gamma * sum_j W[i,j] (decoded_j - decoded_i)``

    (unit row sums).  ``gamma`` is CHOCO-SGD's consensus step size: 1
    takes the full correction (stable for dense codecs, whose replicas
    track the models to quantization error), < 1 damps it (required
    under aggressive top-k, where the replicas lag by the untransmitted
    75%+ of every delta and full steps chase stale disagreements —
    engines pass ``CompressConfig.consensus_gamma``).  Mixing applies only replica *differences* to the
    full local model: where the replicas agree (e.g. a coordinate whose
    deltas nobody has transmitted yet under top-k) ``params_i`` is left
    untouched, instead of shrinking toward ``W[i,i] * params_i`` as
    mixing raw sparse payloads would — that shrinkage is what breaks
    training under top-k, and error feedback cannot undo it (it only
    re-sends what was dropped, later).  ``decoded_i`` is the engine's
    reconstructed replica of node i (``hat_i``, advanced by
    difference coding — see ``CompiledSuperstep``); mathematically the
    form reduces to the plain contraction ``W @ params`` when ``decoded
    == params``, and an identity row (``W[i,:] = e_i``, e.g. an
    isolated node) reconstructs ``params_i`` exactly up to the single
    f32 rounding of ``decoded_i + (params_i - decoded_i)`` (bitwise
    when ``decoded`` is a direct decode of ``params + resid``, by the
    codec's residual identity).  ``mixed``/``decoded`` leaves are f32
    and row-aligned with the local param block (sharded mode passes
    each device's rows); the result casts back to the param leaf dtype.
    """
    g = float(gamma)

    def one(m, p, dc):
        m32 = m.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if g == 1.0:
            # Keep the γ = 1 association (mixed + (params - decoded)) so
            # the damping knob cannot perturb existing full-step runs
            # even at the rounding level.
            return (m32 + (p32 - dc)).astype(p.dtype)
        return (p32 + g * (m32 - dc)).astype(p.dtype)
    return jax.tree_util.tree_map(one, mixed, stacked_params, decoded)


def apply_mixing_compressed(w: jax.Array, stacked_params, decoded,
                            chunk_d: Optional[int] = None,
                            gamma: float = 1.0):
    """Compressed-gossip mixing: the standard row-stochastic contraction
    over the **decoded** payloads, then the consensus-difference
    correction (:func:`apply_consensus_correction`, step size
    ``gamma``).  Same f32/HIGHEST schedule and ``chunk_d`` semantics as
    :func:`apply_mixing`; ``decoded`` leaves are the codec's f32
    output, the result is cast to the param dtypes."""
    w32 = w.astype(jnp.float32)
    mixed = jax.tree_util.tree_map(
        lambda leaf: tensordot_mix_leaf(w32, leaf, chunk_d), decoded)
    return apply_consensus_correction(mixed, stacked_params, decoded,
                                      gamma=gamma)


def mix_numpy(w: np.ndarray, stacked: dict) -> dict:
    """Host-side mixing for the protocol simulator / tiny experiments."""
    out = {}
    for k, v in stacked.items():
        n = v.shape[0]
        out[k] = (w @ v.reshape(n, -1)).reshape(v.shape).astype(v.dtype)
    return out


# ---------------------------------------------------------------------------
# Sanity predicates used by tests and the runtime's debug mode.
# ---------------------------------------------------------------------------

def is_row_stochastic(w: np.ndarray, atol: float = 1e-9) -> bool:
    """Nonnegative entries and unit row sums (every valid mixing W)."""
    return bool(np.all(w >= -atol) and
                np.allclose(w.sum(axis=1), 1.0, atol=atol))

def is_doubly_stochastic(w: np.ndarray, atol: float = 1e-9) -> bool:
    """Row- and column-stochastic (MH weights, fully-connected W)."""
    return is_row_stochastic(w, atol) and is_row_stochastic(w.T, atol)
