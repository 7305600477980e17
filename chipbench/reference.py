"""Plain reference of the benchmark's decentralized rounds.

Straightforward ``jax.numpy`` with every contraction at
``Precision.HIGHEST``, one round at a time from Python, the deferred
acceptance matching as a sequential loop in NumPy.  It imports nothing of
the program under test and takes nothing the program made: it starts from
the benchmark's own seeded weights (the family's ``init_node``, the same
function the benchmark hands the program as ``init_fn``) and the
benchmark's data, and redraws every random choice (batch rows, Gumbel
noise, tie noise, Epidemic peers) from the seeds by the recipes the
paper's engine states.

The node model is the reference half of the configuration's family
(``chipbench/families/<family>_reference.py``): ``init_node(key, model)``,
``loss_and_accuracy(p, batch, model)`` and ``draw(train, rows, sizes, key,
rnd, batch)``, the batches the program's stream draws.  Everything here
works on any parameter pytree.

One round, for every node ``i`` (population stacked on a leading axis):

1. local step: ``p_i <- p_i - lr * grad L(p_i; batch_i)``, the family's
   loss over the node's drawn batch;
2. Morph, every ``delta_r`` rounds: the Eq.-3 similarity (per-leaf
   cosine, averaged over leaves) of the post-step models, Eq.-4
   transitive estimates, Gumbel-top-k selection of ``k`` dissimilar
   peers plus ``view - k`` random known ones, deferred acceptance with
   in- and out-degree at most ``k``, gossip of the known-peer sets;
   Epidemic: every sender picks ``k`` distinct receivers at random;
3. mixing: ``p_i <- mean of p_i and its in-neighbours' models``.

``dtype=bfloat16`` computes all of it in bfloat16 at the default matmul
precision: the control that ``correct`` must reject.

The population lives on the host, as NumPy in the run's dtype, between
steps, so that a node model larger than a chip's share of its population
can still be checked.  The device holds one block of ``b`` nodes at a
time for the local step and for evaluation (:func:`block_size`: all
``n`` wherever three copies of the population fit in half of the
device's free memory), and one leaf's ``[n, ...]`` at a time for mixing
and for the Eq.-3 Gram.  At ``b = n`` the arithmetic is that of one
whole-population step.
"""
from __future__ import annotations

import collections
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
TIE_NOISE = 1e-4          # uniform noise breaking preference ties


class Model(dict):
    """A configuration as a static argument of a jitted function."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def leaf_dtype(p):
    """The dtype of a parameter pytree, which sets the arithmetic."""
    return jax.tree_util.tree_leaves(p)[0].dtype


def precision(dtype):
    """HIGHEST for float32; the default for the bfloat16 control."""
    return HIGHEST if dtype == jnp.float32 else None


def _cast(tree, dtype):
    """Floating leaves of ``tree`` in ``dtype``; the others as they are."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


# ---------------------------------------------------------------------------
# One round's pieces (jitted; n, k and shapes are static)
# ---------------------------------------------------------------------------

def block_size(n: int, node_bytes: int) -> int:
    """Nodes in one block: all ``n`` where three copies of the population
    (a step's params, new params and gradients) fit in half of the default
    device's free memory, else the most that fit, at least 1.  A device
    that reports no memory (the CPU) takes all ``n``."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return n
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    return int(max(1, min(n, free // 2 // (3 * node_bytes))))


@functools.partial(jax.jit,
                   static_argnames=("family", "model", "batch", "lr"))
def local_step(params, train, rows, sizes, key, rnd, start, *, family,
               model, batch, lr):
    """The SGD step of the block of nodes ``start, start + 1, ...`` that
    ``params`` holds, each on its round-``rnd`` batch, which the family
    draws for every node from its row table ``rows[i]`` (its first
    ``sizes[i]`` entries) and the block slices.  Returns the block's new
    params and its gradients."""
    count = jax.tree_util.tree_leaves(params)[0].shape[0]
    drawn = jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, start, count),
        family.draw(train, rows, sizes, key, rnd, batch))

    def one(p, b):
        g = jax.grad(lambda q: family.loss_and_accuracy(q, b, model)[0])(p)
        lr_ = jnp.asarray(lr, leaf_dtype(p))
        return jax.tree_util.tree_map(lambda a, b: a - lr_ * b, p, g), g

    return jax.vmap(one)(params, drawn)


@jax.jit
def _leaf_cos(leaf):
    """``[n, n]`` cosine of every pair of rows of one leaf ``[n, ...]``."""
    n = leaf.shape[0]
    prec = precision(leaf.dtype)
    x = leaf.reshape(n, -1)
    norms = jnp.sqrt((x * x).sum(axis=1))
    cos = jnp.dot(x, x.T, precision=prec) / jnp.maximum(
        norms[:, None] * norms[None, :], 1e-12)
    return cos.astype(jnp.float32)


def similarity(params):
    """Eq. 3: ``[n, n]`` mean over leaves of the per-leaf cosine of every
    pair of node models, one leaf on the device at a time."""
    leaves = jax.tree_util.tree_leaves(params)
    total = 0.0
    for leaf in leaves:
        total = total + _leaf_cos(leaf)
    return total / len(leaves)


@jax.jit
def _weights(edges):
    """Uniform averaging over each node and its in-neighbours."""
    n = edges.shape[0]
    w = edges.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32)
    return w / w.sum(axis=1, keepdims=True)


@jax.jit
def _mix_leaf(w, leaf):
    x = leaf.reshape(leaf.shape[0], -1)
    out = jnp.dot(w.astype(x.dtype), x, precision=precision(x.dtype))
    return out.reshape(leaf.shape).astype(leaf.dtype)


def mix(params, edges):
    """Uniform averaging over each node and its in-neighbours, in place
    in the host population ``params``, one leaf on the device at a
    time."""
    w = _weights(edges)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf[...] = np.asarray(_mix_leaf(w, leaf))


@functools.partial(jax.jit, static_argnames=("family", "model"))
def evaluate(params, test, *, family, model):
    """Per node of the block ``params`` (one node at a time): mean test
    loss and accuracy."""
    def one(p):
        loss, acc = family.loss_and_accuracy(p, test, model)
        return loss.astype(jnp.float32), acc
    return jax.lax.map(one, params)


@functools.partial(jax.jit, static_argnames=("n", "k"))
def epidemic_edges(key, rnd, *, n, k):
    """EL-Oracle: each sender picks ``k`` distinct receivers by the top
    ``k`` of Gumbel noise ``gumbel(fold_in(key, rnd), [n, n])`` (row =
    sender, self excluded); returns ``edges[receiver, sender]``."""
    gum = jax.random.gumbel(jax.random.fold_in(key, rnd), (n, n),
                            jnp.float32)
    eye = jnp.eye(n, dtype=bool)
    _, idx = jax.lax.top_k(jnp.where(eye, NEG_INF, gum), k)
    out = jnp.zeros((n, n), bool).at[jnp.arange(n)[:, None], idx].set(True)
    return out.T


# ---------------------------------------------------------------------------
# Morph negotiation
# ---------------------------------------------------------------------------

MorphState = collections.namedtuple("MorphState",
                                    "known sim valid edges key")


def morph_init(key, n):
    """Bootstrap: every node knows and receives from its two ring
    neighbours; no similarity estimate yet."""
    ring = np.roll(np.eye(n, dtype=bool), 1, axis=1) \
        | np.roll(np.eye(n, dtype=bool), -1, axis=1)
    return MorphState(jnp.asarray(ring), jnp.zeros((n, n), jnp.float32),
                      jnp.zeros((n, n), bool), jnp.asarray(ring), key)


@functools.partial(jax.jit, static_argnames=("k", "view", "beta"))
def morph_preferences(st, true_sim, *, k, view, beta):
    """Everything of a negotiation before the matching.

    The state's key splits into (next key, selection, receiver ties,
    sender ties).  Direct estimates are taken on current in-edges;
    missing ones come from Eq. 4, ``mean_y sim(i,y) sim(y,z)`` over
    informants ``y`` valid on both sides.  Node ``i`` (its key the
    ``i``-th split of the selection key, split again in two) wants the
    top ``k`` of ``-beta * sim + gumbel`` among known peers with an
    estimate, plus the top ``view - k`` of gumbel noise among its other
    known peers.  Receivers rank wanted peers first (the dissimilar
    ahead), then estimated, then the rest; senders rank requesters by
    the requesters' ranking, each side with its own uniform tie noise.
    """
    n = true_sim.shape[0]
    key, k_sel, k_tr, k_ts = jax.random.split(st.key, 4)
    eye = jnp.eye(n, dtype=bool)
    sim = jnp.where(st.edges, true_sim, st.sim)
    valid = st.valid | st.edges
    m = (valid[:, :, None] & valid.T[None, :, :]).astype(jnp.float32)
    num = jnp.einsum("iy,iyz,yz->iz", sim, m, sim, precision=HIGHEST)
    cnt = m.sum(axis=1)
    sim = jnp.where(valid, sim, num / jnp.maximum(cnt, 1.0))
    valid = valid | (cnt > 0)
    cand = valid & st.known & ~eye
    full = st.known & ~eye

    def node(key_i, sim_i, cand_i, full_i):
        kb, kr = jax.random.split(key_i)
        g = jax.random.gumbel(kb, (n,), jnp.float32)
        _, idx = jax.lax.top_k(jnp.where(cand_i, -beta * sim_i + g,
                                         NEG_INF), k)
        ok = cand_i[idx] & (jnp.arange(k) < cand_i.sum())
        want = jnp.zeros((n,), bool).at[idx].max(ok)
        pool = full_i & ~cand_i & ~want
        r = view - k
        if r <= 0:
            return want
        g = jax.random.gumbel(kr, (n,), jnp.float32)
        _, ridx = jax.lax.top_k(jnp.where(pool, g, NEG_INF), r)
        ok = pool[ridx] & (jnp.arange(r) < pool.sum())
        return want.at[ridx].max(ok)

    want = jax.vmap(node)(jax.random.split(k_sel, n), sim, cand, full)
    recv = (jnp.where(cand, -sim, 0.0) + jnp.where(want, 2.0, 0.0)
            + jnp.where(full & ~want, -4.0, 0.0)
            + jax.random.uniform(k_tr, (n, n), jnp.float32, 0.0,
                                 TIE_NOISE))
    send = recv.T + jax.random.uniform(k_ts, (n, n), jnp.float32, 0.0,
                                       TIE_NOISE)
    return sim, valid, full, recv, send, key


def deferred_acceptance(recv, send, allowed, k_in, k_out):
    """Receiver-proposing deferred acceptance: each receiver proposes to
    its allowed senders in order of ``recv[i]`` (descending, lower index
    first on a tie) until it holds ``k_in``; a sender holding more than
    ``k_out`` drops its least preferred by ``send[j]`` (the higher index
    on a tie), who proposes on.  Returns ``edges[receiver, sender]``."""
    n = recv.shape[0]
    allowed = allowed & ~np.eye(n, dtype=bool)
    order = []
    for i in range(n):
        cols = np.flatnonzero(allowed[i])
        order.append(cols[np.argsort(-recv[i, cols], kind="stable")])
    nxt = np.zeros(n, int)
    held = [[] for _ in range(n)]
    count = np.zeros(n, int)
    queue = collections.deque(range(n))
    while queue:
        i = queue.popleft()
        while count[i] < k_in and nxt[i] < len(order[i]):
            j = order[i][nxt[i]]
            nxt[i] += 1
            held[j].append(i)
            count[i] += 1
            if len(held[j]) > k_out:
                worst = min(held[j], key=lambda r: (send[j, r], -r))
                held[j].remove(worst)
                count[worst] -= 1
                if worst != i:
                    queue.append(worst)
    edges = np.zeros((n, n), bool)
    for j in range(n):
        edges[held[j], j] = True
    return edges


@jax.jit
def morph_after(st, true_sim, sim, valid, edges, key):
    """Matched edges give direct estimates; each receiver learns every
    peer its senders know."""
    n = edges.shape[0]
    eye = jnp.eye(n, dtype=bool)
    sim = jnp.where(edges, true_sim, sim)
    valid = valid | edges
    reach = (edges.astype(jnp.int32)
             @ (st.known | eye).astype(jnp.int32)) > 0
    return MorphState((st.known | reach) & ~eye, sim, valid, edges, key)


def morph_negotiate(st, params, *, k, view, beta):
    true_sim = similarity(params)
    sim, valid, full, recv, send, key = morph_preferences(
        st, true_sim, k=k, view=view, beta=beta)
    edges = deferred_acceptance(np.asarray(recv), np.asarray(send),
                                np.asarray(full), k, k)
    return morph_after(st, true_sim, sim, valid, jnp.asarray(edges), key)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@jax.jit
def _sum_squares(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree)


def _put(population, start, block):
    """Write the host ``block`` into rows ``start, ...`` of the host
    ``population``, leaf by leaf."""
    for dst, src in zip(jax.tree_util.tree_leaves(population),
                        jax.tree_util.tree_leaves(block)):
        dst[start:start + len(src)] = src


def run(*, seeds, family, model, traffic, train, parts, test, rounds,
        dtype=jnp.float32, block=None):
    """``rounds`` rounds from the seeded start, evaluated after the first
    and the last.  ``family`` is the reference half of the
    configuration's family, ``train`` and ``test`` the pytrees its
    ``build`` made.  ``seeds`` holds the integer seeds of the weights
    (``init``), the strategy (``strategy``) and the batch draws
    (``stream``).  ``block`` forces the nodes a block (tests); by default
    :func:`block_size` sets it.  Returns host arrays: ``p0`` (start),
    ``p1`` (after round 0), ``p_end``, ``grad0`` (the norm of each leaf
    of round 0's gradients over all nodes), ``edges`` ``[rounds, n, n]``,
    ``evals`` = [(losses [n], accuracy [n])] after round 0 and after the
    last round, and ``block``, the nodes a block."""
    n = traffic["nodes"]
    k = min(traffic["k"], n - 1)
    model = Model(model)
    init_one = functools.partial(family.init_node, model=model)
    init = jax.jit(jax.vmap(init_one))
    shapes = jax.eval_shape(init_one, jax.random.PRNGKey(0))
    node_bytes = sum(math.prod(x.shape) for x in
                     jax.tree_util.tree_leaves(shapes)) \
        * jnp.dtype(dtype).itemsize
    b = block or block_size(n, node_bytes)
    starts = range(0, n, b)
    keys = jax.random.split(jax.random.PRNGKey(seeds["init"]), n)
    p0 = jax.tree_util.tree_map(
        lambda x: np.empty((n,) + x.shape, x.dtype), shapes)
    for s in starts:
        _put(p0, s, jax.device_get(init(keys[s:s + b])))
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), p0)
    size = max(len(q) for q in parts)
    rows = jnp.asarray(np.stack([np.resize(q, size) for q in parts])
                       .astype(np.int32))
    sizes = jnp.asarray([len(q) for q in parts], jnp.int32)
    train, test = _cast(train, dtype), _cast(test, dtype)
    stream_key = jax.random.PRNGKey(seeds["stream"])
    strat_key = jax.random.PRNGKey(seeds["strategy"])
    morph = traffic["strategy"] == "morph"
    st = morph_init(strat_key, n) if morph else None
    out = {"p0": p0, "edges": [], "evals": [], "block": b}
    for rnd in range(rounds):
        squares = None
        for s in starts:
            new, g = local_step(
                jax.tree_util.tree_map(lambda x: x[s:s + b], p), train,
                rows, sizes, stream_key, rnd, s, family=family,
                model=model, batch=traffic["batch"], lr=traffic["lr"])
            _put(p, s, jax.device_get(new))
            if rnd == 0:
                sq = jax.device_get(_sum_squares(g))
                squares = sq if squares is None else jax.tree_util.tree_map(
                    np.add, squares, sq)
            del new, g
        if morph:
            if rnd % traffic["delta_r"] == 0:
                st = morph_negotiate(st, p, k=k,
                                     view=min(k + 2, n - 1), beta=500.0)
            edges = st.edges
        else:
            edges = epidemic_edges(strat_key, rnd, n=n, k=k)
        mix(p, edges)
        out["edges"].append(np.asarray(edges))
        if rnd in (0, rounds - 1):
            evals = [jax.device_get(evaluate(
                jax.tree_util.tree_map(lambda x: x[s:s + b], p), test,
                family=family, model=model)) for s in starts]
            out["evals"].append(tuple(
                np.concatenate([e[i] for e in evals]).astype(np.float64)
                for i in (0, 1)))
            snap = jax.tree_util.tree_map(
                lambda x: np.array(x, np.float32), p)
            out["p1" if rnd == 0 else "p_end"] = snap
        if rnd == 0:
            out["grad0"] = jax.tree_util.tree_map(
                lambda x: float(np.sqrt(x)), squares)
    out["edges"] = np.stack(out["edges"])
    return out


def __getattr__(name):
    """``arch`` and ``init_node(key, arch)`` of the CNN family under their
    former names here, for callers outside the benchmark
    (``tests/test_pool_grad.py``)."""
    if name in ("arch", "init_node"):
        from chipbench.harness import load_family
        cnn = load_family("cnn")[1]
        return cnn.arch if name == "arch" else cnn.init_arch
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
