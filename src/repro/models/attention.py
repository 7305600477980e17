"""GQA attention with RoPE, causal / sliding-window / cross variants and a
functional KV cache for decode.

Shapes: activations ``[batch, seq, d_model]``; caches
``{"k","v": [batch, max_len, kv_heads, head_dim], "pos": scalar}``.

The sliding-window mask is the beyond-paper mechanism that lets dense
full-attention architectures lower the ``long_500k`` decode shape
(DESIGN.md §4); window=None keeps exact full attention.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers

NEG_INF = -1e30


def attn_params(key, cfg, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    q_dim = cfg.num_heads * hd
    kv_dim = cfg.num_kv_heads * hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "q": layers.dense_params(kq, d, q_dim, dtype, bias=cfg.qkv_bias),
        "k": layers.dense_params(kk, d, kv_dim, dtype, bias=cfg.qkv_bias),
        "v": layers.dense_params(kv, d, kv_dim, dtype, bias=cfg.qkv_bias),
        "o": layers.dense_params(ko, q_dim, d, dtype),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _repeat_kv(x, groups: int):
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


def _sdpa(q, k, v, mask, head_dim):
    """q: [b,s,h,hd], k/v: [b,t,h,hd], mask: broadcastable [b,1,s,t]."""
    scale = 1.0 / math.sqrt(head_dim)
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
    return out


def causal_mask(q_pos: jax.Array, k_pos: jax.Array,
                window: Optional[int]) -> jax.Array:
    """[..., q, k] boolean mask: causal, optionally sliding-window."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


# Sequences at least this long take the chunked (flash-style) path; the
# [b, h, s, t] logits of the naive path stop fitting around here.
CHUNKED_THRESHOLD = 2048
Q_CHUNK = 1024
KV_CHUNK = 1024


def _pick_chunk(s: int, target: int = Q_CHUNK, floor: int = 128) -> int:
    """Largest power-of-two divisor of ``s`` in [floor, target] (VLM
    prefill lengths like 32512 = 254*128 are not 1024-divisible)."""
    c = target
    while c >= floor:
        if s % c == 0:
            return c
        c //= 2
    return 0


def _mesh_axis(name: str) -> int:
    """Size of a mesh axis in the current mesh context (1 if absent).
    Callers that trace under a mesh enter it with ``jax.set_mesh``
    (the dry-run and the launcher do)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.shape[name] if name in mesh.axis_names else 1


def _bh_sharding(x):
    """Shard the fused (batch*heads) leading axis over ``model`` when
    divisible — keeps every flash einsum local to its shard (one clean
    parallel axis instead of SPMD factoring heads x head_dim and
    ALL-REDUCING the attention logits)."""
    from jax.sharding import PartitionSpec as P
    msize = _mesh_axis("model")
    if msize <= 1 or x.shape[0] % msize != 0:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, P(*(("model",) + (None,) * (x.ndim - 1))))
    except Exception:
        return x


def _flash_attention(q, k, v, q_pos, k_pos, window, head_dim,
                     q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK):
    """Blockwise attention with online softmax (memory O(qc*kc) per step).

    q: [b, s, h, hd]; k/v: [b, t, h, hd] (kv already head-repeated);
    q_pos: [b, s]; k_pos: [b, t].  Causal + optional sliding window.

    (b, h) are fused into one leading axis, sharded over ``model`` when
    divisible (b*h covers every assigned arch even when h alone does
    not divide the 16-way axis) — see EXPERIMENTS.md §Perf, llama3
    iteration 2.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    if s % q_chunk != 0 or t % kv_chunk != 0:
        raise ValueError(f"seq {s}/{t} not divisible by chunks "
                         f"{q_chunk}/{kv_chunk}")
    nq, nk = s // q_chunk, t // kv_chunk
    bh = b * h
    scale = 1.0 / math.sqrt(head_dim)
    # Fuse (b, h) ONLY when heads alone do not divide the model axis:
    # divisible-head archs already get clean SPMD head sharding, and the
    # merge reshape would break it (measured regression on qwen/nemotron/
    # deepseek — EXPERIMENTS.md §Perf).
    msize = _mesh_axis("model")
    fuse = msize > 1 and h % msize != 0 and bh % msize == 0

    if fuse:
        qs = _bh_sharding(
            q.transpose(0, 2, 1, 3).reshape(bh, nq, q_chunk, hd))
        ks = _bh_sharding(
            k.transpose(0, 2, 1, 3).reshape(bh, nk, kv_chunk, hd))
        vs = _bh_sharding(
            v.transpose(0, 2, 1, 3).reshape(bh, nk, kv_chunk, hd))
    else:
        qs = q.reshape(b, nq, q_chunk, h, hd).transpose(0, 3, 1, 2, 4)
        ks = k.reshape(b, nk, kv_chunk, h, hd).transpose(0, 3, 1, 2, 4)
        vs = v.reshape(b, nk, kv_chunk, h, hd).transpose(0, 3, 1, 2, 4)
    qp = q_pos.reshape(b, nq, q_chunk)
    kp = k_pos.reshape(b, nk, kv_chunk)

    def q_block(qi: int, kv_lo: int, kv_hi: int):
        """One (unrolled) q chunk attending kv chunks [kv_lo, kv_hi)."""
        qpb = qp[:, qi]                             # [b, qc]
        if fuse:
            qb = qs[:, qi]                          # [bh, qc, hd]
            lead = (bh,)
            eq, ev = "bqd,bkd->bqk", "bqk,bkd->bqd"
        else:
            qb = qs[:, :, qi]                       # [b, h, qc, hd]
            lead = (b, h)
            eq, ev = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
        m0 = jnp.full(lead + (q_chunk,), NEG_INF, jnp.float32)
        l0 = jnp.zeros(lead + (q_chunk,), jnp.float32)
        a0 = jnp.zeros(lead + (q_chunk, hd), jnp.float32)

        def kv_body(carry, kj):
            m, l, acc = carry
            kk = ks[:, kj] if fuse else ks[:, :, kj]
            vv = vs[:, kj] if fuse else vs[:, :, kj]
            logits = jnp.einsum(eq, qb, kk,
                                preferred_element_type=jnp.float32) * scale
            mask = causal_mask(qpb, kp[:, kj], window)     # [b, qc, kc]
            if fuse:
                mask = jnp.broadcast_to(
                    mask[:, None], (b, h) + mask.shape[1:]).reshape(
                    bh, q_chunk, kv_chunk)
            else:
                mask = mask[:, None]
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(-1))
            corr = jnp.exp(m - m_new)
            p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] \
                + jnp.einsum(ev, p.astype(vv.dtype),
                             vv).astype(jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      jnp.arange(kv_lo, kv_hi))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)          # [bh|b,h, qc, hd]

    # The outer q loop is UNROLLED so each q chunk's kv range is static:
    # fully-masked kv chunks (above the causal diagonal, or outside the
    # sliding window) are never visited — ~2x fewer inner steps for
    # causal, more for windowed (EXPERIMENTS.md §Perf iteration 3).
    same_grid = (s == t)                 # self-attn: chunk i ends at
    outs = []                            # position (i+1)*qc - 1
    for qi in range(nq):
        if same_grid and q_chunk == kv_chunk:
            hi = qi + 1
            lo = 0
            if window is not None:
                lo = max(0, (qi * q_chunk - window) // kv_chunk)
        else:
            lo, hi = 0, nk
        outs.append(q_block(qi, lo, hi))
    if fuse:
        out = jnp.stack(outs, axis=1)               # [bh, nq, qc, hd]
        return (out.reshape(bh, s, hd).reshape(b, h, s, hd)
                .transpose(0, 2, 1, 3))
    out = jnp.stack(outs, axis=2)                   # [b, h, nq, qc, hd]
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def self_attention(p, x, cfg, *, positions: jax.Array,
                   causal: bool = True,
                   window: Optional[int] = None) -> jax.Array:
    b, s, _ = x.shape
    groups = cfg.num_heads // cfg.num_kv_heads
    q = _split_heads(layers.dense(p["q"], x), cfg.num_heads, cfg.head_dim)
    k = _split_heads(layers.dense(p["k"], x), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(layers.dense(p["v"], x), cfg.num_kv_heads, cfg.head_dim)
    if cfg.rope_theta is not None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    chunk = _pick_chunk(s)
    if causal and s >= CHUNKED_THRESHOLD and chunk:
        out = _flash_attention(q, k, v, positions, positions, window,
                               cfg.head_dim, q_chunk=chunk,
                               kv_chunk=chunk)
    else:
        if causal:
            mask = causal_mask(positions, positions, window)[:, None]
        else:
            mask = jnp.ones((b, 1, s, s), bool)
        out = _sdpa(q, k, v, mask, cfg.head_dim)
    return layers.dense(p["o"], out.reshape(b, s, -1))


def cross_attention(p, x, memory, cfg) -> jax.Array:
    """Decoder->encoder attention (no RoPE, full visibility)."""
    b, s, _ = x.shape
    t = memory.shape[1]
    groups = cfg.num_heads // cfg.num_kv_heads
    q = _split_heads(layers.dense(p["q"], x), cfg.num_heads, cfg.head_dim)
    k = _split_heads(layers.dense(p["k"], memory),
                     cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(layers.dense(p["v"], memory),
                     cfg.num_kv_heads, cfg.head_dim)
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    mask = jnp.ones((b, 1, s, t), bool)
    out = _sdpa(q, k, v, mask, cfg.head_dim)
    return layers.dense(p["o"], out.reshape(b, s, -1))


# ---------------------------------------------------------------------------
# KV-cache decode path.
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype) -> Dict[str, Any]:
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _constrain(x, spec):
    """Best-effort sharding hint (no-op outside a mesh context)."""
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def decode_self_attention(p, x, cfg, cache, pos: jax.Array,
                          window: Optional[int] = None,
                          kv_spec=None
                          ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode. x: [b, 1, d]; ``pos``: scalar current position.

    With a sliding window the production deployment sizes the buffer as a
    **ring of exactly ``window`` slots** (``max_len == window`` triggers
    ring mode: slot = pos % window, all slots valid once wrapped) — this
    is what makes ``long_500k`` affordable for windowed dense archs.
    Otherwise the buffer is linear in ``max_len``.
    """
    b = x.shape[0]
    groups = cfg.num_heads // cfg.num_kv_heads
    q = _split_heads(layers.dense(p["q"], x), cfg.num_heads, cfg.head_dim)
    k = _split_heads(layers.dense(p["k"], x), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(layers.dense(p["v"], x), cfg.num_kv_heads, cfg.head_dim)
    positions = jnp.full((b, 1), pos, jnp.int32)
    if cfg.rope_theta is not None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    max_len = cache["k"].shape[1]
    ring = window is not None and max_len == window
    slot = pos % max_len if ring else pos
    # Pin the single-token update to the cache's sharding BEFORE the
    # dynamic-update-slice: resharding the [b,1,kvh,hd] update is free,
    # while letting SPMD reshard the multi-GB cache operand instead
    # triggers an involuntary full rematerialization per layer per step.
    k = _constrain(k.astype(cache["k"].dtype), kv_spec)
    v = _constrain(v.astype(cache["v"].dtype), kv_spec)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    ck = _constrain(ck, kv_spec)
    cv = _constrain(cv, kv_spec)
    k_pos = jnp.arange(max_len)[None, :]                  # [1, t]
    if ring:
        # slots wrap: before the first wrap only slots <= pos are live,
        # afterwards every slot holds an in-window key.
        mask = (k_pos <= pos) | (pos >= max_len)
    else:
        mask = (k_pos <= pos)
        if window is not None:
            mask &= k_pos > pos - window
    mask = mask[:, None, None, :]                         # [1,1,1,t]
    kk = _repeat_kv(ck, groups)
    vv = _repeat_kv(cv, groups)
    out = _sdpa(q, kk, vv, mask, cfg.head_dim)
    y = layers.dense(p["o"], out.reshape(b, 1, -1))
    return y, {"k": ck, "v": cv}
