"""In-graph (jit-compiled) Morph controller — the TPU-native formulation.

``core.protocol`` is the message-faithful reference; this module is the
production path: the *entire* topology update runs inside one XLA program
alongside training, so a Δ_r-round superstep (local steps → similarity →
selection → matching → mixing) is a single compiled computation with no
host round-trips.

Mapping of the paper's mechanisms onto jax.lax:

=====================  ====================================================
paper mechanism         in-graph realization
=====================  ====================================================
Eq. 3 per-layer cosine  ``pairwise_model_similarity`` (or the Pallas
                        ``pairwise_cosine`` kernel on flattened layers)
Eq. 5 sequential        Gumbel-top-k over ``-beta * sim`` (provably the
softmax sampling        same distribution; see tests/test_selection.py)
Alg. 3 random set R     uniform Gumbel-top-k over the complement pool
college admission       bounded deferred acceptance on dense masks
                        (``matching.match_jax``)
partial views P_i       per-node boolean known-peer masks, OR-diffused
                        along accepted edges (gossip discovery)
Alg. 2 l.12 averaging   row-stochastic mixing over the node axis
=====================  ====================================================

The controller is deliberately *global-state-free*: its entire state is a
:class:`MorphGraphState` pytree, so it shards/vmaps/checkpoints like any
other training state.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .matching import match_jax
from .mixing import apply_mixing, uniform_weights_jax
from .selection import NEG_INF, sample_gumbel_topk, softmax_logits
from .similarity import pairwise_model_similarity


class MorphGraphState(NamedTuple):
    """Device-resident controller state (leading axis = node where [n,...])."""
    known: jax.Array          # [n, n] bool — partial views P_i
    sim: jax.Array            # [n, n] f32 — latest similarity estimates
    sim_valid: jax.Array      # [n, n] bool — which estimates are usable (C_A)
    edges: jax.Array          # [n, n] bool — current in-edge matrix
    key: jax.Array            # PRNG key


def init_state(key: jax.Array, initial_adj: jax.Array) -> MorphGraphState:
    """Bootstrap controller state from an [n, n] adjacency (the initial
    overlay, self-loops stripped): known peers = current edges = the
    bootstrap graph, similarity estimates empty."""
    n = initial_adj.shape[0]
    adj = initial_adj.astype(bool) & ~jnp.eye(n, dtype=bool)
    return MorphGraphState(
        known=adj,
        sim=jnp.zeros((n, n), jnp.float32),
        sim_valid=jnp.zeros((n, n), bool),
        edges=adj,
        key=key,
    )


def _tie_noise(key: jax.Array, shape) -> jax.Array:
    return jax.random.uniform(key, shape, jnp.float32, 0.0, 1e-4)


def update_topology(state: MorphGraphState,
                    stacked_params,
                    k: int,
                    view_size: int,
                    beta: float,
                    match_rounds: Optional[int] = None,
                    sim_fn=pairwise_model_similarity,
                    k_out: Optional[int] = None,
                    ) -> Tuple[MorphGraphState, jax.Array]:
    """One Δ_r negotiation: returns ``(new_state, W)``.

    ``sim_fn`` computes the [n, n] Eq.-3 matrix from the stacked params —
    injectable so the Pallas kernel / a cheaper probe can be swapped in.
    ``k_out`` caps per-sender out-degree (default ``k`` — the paper's
    tight market; ``k + 1`` is the capacity-slack alternative the fig67
    replay evaluates).
    """
    n = state.known.shape[0]
    key, k_sel, k_tie_r, k_tie_s = jax.random.split(state.key, 4)
    eye = jnp.eye(n, dtype=bool)

    # --- measurements: a node can evaluate Eq. 3 against every model it
    # currently receives (its in-edges) — update direct estimates.
    true_sim = sim_fn(stacked_params).astype(jnp.float32)
    direct = state.edges
    sim = jnp.where(direct, true_sim, state.sim)
    sim_valid = state.sim_valid | direct

    # --- transitive estimates (Eq. 4) for peers we know only indirectly:
    # sim^(i,z) = mean_y sim(i,y) * sim(y,z) over shared informants y.
    inf_mask = (sim_valid[:, :, None] & sim_valid.T[None, :, :]
                ).astype(jnp.float32)                    # [i, y, z]
    est_num = jnp.einsum("iy,iyz,yz->iz", sim, inf_mask, sim,
                         precision=jax.lax.Precision.HIGHEST)
    est_cnt = jnp.einsum("iyz->iz", inf_mask)
    est = est_num / jnp.maximum(est_cnt, 1.0)
    est_ok = est_cnt > 0
    sim = jnp.where(sim_valid, sim, est)
    sim_valid = sim_valid | est_ok

    # --- Alg. 3 per node (vmapped): k diversity picks + (s-k) random.
    keys = jax.random.split(k_sel, n)
    cand = sim_valid & state.known & ~eye                 # C_A
    full = state.known & ~eye                             # C

    def per_node(key_i, sim_i, cand_i, full_i):
        kb, kr = jax.random.split(key_i)
        bidx, bvalid = sample_gumbel_topk(kb, sim_i, cand_i, k, beta)
        want = jnp.zeros((n,), bool).at[bidx].max(bvalid, mode="drop")
        pool = full_i & ~cand_i & ~want
        r = view_size - k
        if r > 0:
            gum = jax.random.gumbel(kr, (n,), jnp.float32)
            scores = jnp.where(pool, gum, NEG_INF)
            _, ridx = jax.lax.top_k(scores, r)
            rvalid = jnp.take(pool, ridx) & (jnp.arange(r) < pool.sum())
            want = want.at[ridx].max(rvalid, mode="drop")
        return want

    want = jax.vmap(per_node)(keys, sim, cand, full)      # [n, n] bool

    # --- college-admission matching.  Receiver prefers dissimilar senders
    # (unknown-similarity random picks rank by their injected noise);
    # senders rank requesters by the requester-reported dissimilarity.
    # Rejected receivers fall back to their remaining known peers at a
    # strictly lower preference tier ("look for another connection to
    # maintain k", §III-B) so supply-side rejections cannot leave nodes
    # under-filled while supply exists.
    fallback = full & ~want
    recv_pref = (jnp.where(cand, -sim, 0.0)
                 + jnp.where(want, 2.0, 0.0)
                 + jnp.where(fallback, -4.0, 0.0)
                 + _tie_noise(k_tie_r, (n, n)))
    send_pref = recv_pref.T + _tie_noise(k_tie_s, (n, n))
    edges = match_jax(recv_pref, send_pref, want | fallback, k,
                      k if k_out is None else k_out, match_rounds)

    # --- every matched edge delivers a model this round, so the receiver
    # takes a direct Eq. 3 measurement on it (protocol: receive_model) —
    # without this, freshly matched edges would keep stale transitive
    # estimates until the *next* negotiation.
    sim = jnp.where(edges, true_sim, sim)
    sim_valid = sim_valid | edges

    # --- gossip discovery: receiving from j teaches i everything j knows.
    reach = (edges.astype(jnp.int32) @
             (state.known | eye).astype(jnp.int32)) > 0
    known = (state.known | reach) & ~eye

    w = uniform_weights_jax(edges)
    new_state = MorphGraphState(known=known, sim=sim, sim_valid=sim_valid,
                                edges=edges, key=key)
    return new_state, w


def mix_round(state: MorphGraphState, stacked_params):
    """Between negotiations: reuse current edges (Alg. 2 keeps the neighbor
    set for Δ_r rounds) and apply uniform averaging."""
    w = uniform_weights_jax(state.edges)
    return apply_mixing(w, stacked_params)
