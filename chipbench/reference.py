"""Plain reference of the benchmark's decentralized rounds.

Straightforward ``jax.numpy`` with every contraction at
``Precision.HIGHEST``, one round at a time from Python, the deferred
acceptance matching as a sequential loop in NumPy.  It imports nothing of
the program under test and takes nothing the program made: it starts from
the benchmark's own seeded weights (:func:`init_node`, the same function
the benchmark hands the program as ``init_fn``) and the benchmark's data,
and redraws every random choice (batch rows, Gumbel noise, tie noise,
Epidemic peers) from the seeds by the recipes the paper's engine states.

One round, for every node ``i`` (population stacked on a leading axis):

1. local step: ``p_i <- p_i - lr * grad L(p_i; batch_i)``, the
   configuration's layers in order (``layers``: convolutions, GroupNorm,
   ReLU, 2x2 max-pool, linear), mean cross-entropy over the batch;
2. Morph, every ``delta_r`` rounds: the Eq.-3 similarity (per-leaf
   cosine, averaged over leaves) of the post-step models, Eq.-4
   transitive estimates, Gumbel-top-k selection of ``k`` dissimilar
   peers plus ``view - k`` random known ones, deferred acceptance with
   in- and out-degree at most ``k``, gossip of the known-peer sets;
   Epidemic: every sender picks ``k`` distinct receivers at random;
3. mixing: ``p_i <- mean of p_i and its in-neighbours' models``.

``dtype=bfloat16`` computes all of it in bfloat16 at the default matmul
precision: the control that ``correct`` must reject.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
TIE_NOISE = 1e-4          # uniform noise breaking preference ties
GN_EPS = 1e-5


# ---------------------------------------------------------------------------
# The node model, from the configuration's layer list
# ---------------------------------------------------------------------------

def arch(model: dict) -> tuple:
    """The node model of a configuration as a hashable (static) value:
    ``(image_size, in_channels, layers)``."""
    return (model["image_size"], model["in_channels"],
            tuple(tuple(layer) for layer in model["layers"]))


def param_layers(arch: tuple) -> list:
    """``[(name, kind, shapes)]`` for each layer of the architecture
    that holds weights, in order, with the shapes of its leaves: a conv
    ``["conv", out, k]`` (``k x k``, SAME, with bias) is ``conv<i>``, a
    ``["group_norm", groups]`` ``gn<i>``, a ``["dense", out]`` ``fc<i>``.
    ``relu``, ``pool`` (2x2 max, stride 2) and ``flatten`` hold none."""
    h, c, layers = arch
    feat, out, seen = None, [], collections.Counter()
    for layer in layers:
        kind = layer[0]
        if kind == "conv":
            cout, k = layer[1], layer[2]
            seen["conv"] += 1
            out.append((f"conv{seen['conv']}", kind,
                        {"w": (k, k, c, cout), "b": (cout,)}))
            c = cout
        elif kind == "group_norm":
            seen["gn"] += 1
            out.append((f"gn{seen['gn']}", kind,
                        {"scale": (c,), "bias": (c,)}))
        elif kind == "pool":
            h //= 2
        elif kind == "flatten":
            feat = h * h * c
        elif kind == "dense":
            seen["fc"] += 1
            out.append((f"fc{seen['fc']}", kind,
                        {"w": (feat, layer[1]), "b": (layer[1],)}))
            feat = layer[1]
        elif kind != "relu":
            raise ValueError(f"unknown layer {layer!r}")
    return out


def init_node(key, arch: tuple):
    """One node's weights: He-scaled truncated normals for the convs,
    ``1/sqrt(fan_in)`` for the linear layers, zero biases, unit GroupNorm
    scales.  The benchmark gives this same function to the program as
    its ``init_fn``, so both start from the same seeded weights."""
    layers = param_layers(arch)
    keys = jax.random.split(key, len(layers))
    params = {}
    for k, (name, kind, shapes) in zip(keys, layers):
        if kind == "group_norm":
            params[name] = {"scale": jnp.ones(shapes["scale"], jnp.float32),
                            "bias": jnp.zeros(shapes["bias"], jnp.float32)}
            continue
        w = shapes["w"]
        fan_in = math.prod(w[:-1])
        std = math.sqrt((2.0 if kind == "conv" else 1.0) / fan_in)
        params[name] = {"w": jax.random.truncated_normal(
            k, -2.0, 2.0, w, jnp.float32) * std,
            "b": jnp.zeros(shapes["b"], jnp.float32)}
    return params


def _dtype(p):
    return jax.tree_util.tree_leaves(p)[0].dtype


def _prec(dtype):
    return HIGHEST if dtype == jnp.float32 else None


def forward(p, x, arch: tuple):
    """Logits ``[b, classes]`` of images ``x [b, H, W, C]`` through the
    layers of ``arch``; the dtype of ``p`` sets the arithmetic."""
    prec = _prec(_dtype(p))
    names = iter(name for name, *_ in param_layers(arch))
    h = x
    for layer in arch[2]:
        kind = layer[0]
        if kind == "conv":
            q = p[next(names)]
            h = jax.lax.conv_general_dilated(
                h, q["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=prec) + q["b"]
        elif kind == "group_norm":
            q, groups = p[next(names)], layer[1]
            b, hh, ww, c = h.shape
            g = h.reshape(b, hh, ww, groups, c // groups)
            mu = g.mean(axis=(1, 2, 4), keepdims=True)
            var = ((g - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
            g = (g - mu) / jnp.sqrt(var + GN_EPS)
            h = g.reshape(b, hh, ww, c) * q["scale"] + q["bias"]
        elif kind == "relu":
            h = jnp.maximum(h, 0)
        elif kind == "pool":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        elif kind == "flatten":
            h = h.reshape(h.shape[0], -1)
        else:
            q = p[next(names)]
            h = jnp.dot(h, q["w"], precision=prec) + q["b"]
    return h


def loss_and_correct(p, images, labels, arch: tuple):
    """Mean cross-entropy and the number of correct predictions."""
    logits = forward(p, images.astype(_dtype(p)), arch)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return nll.mean(), (logits.argmax(-1) == labels).sum()


# ---------------------------------------------------------------------------
# One round's pieces (jitted; n, k and shapes are static)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("arch", "batch", "lr"))
def local_step(params, data_images, data_labels, rows, sizes, key, rnd,
               *, arch, batch, lr):
    """Every node's SGD step on its round-``rnd`` batch: node ``i`` draws
    ``batch`` rows uniformly, with replacement, from the first
    ``sizes[i]`` entries of its row table ``rows[i]``, by
    ``randint(fold_in(fold_in(key, rnd), i))``.  Returns the new params
    and the gradients."""
    k_round = jax.random.fold_in(key, rnd)

    def one(p, table, size, i):
        take = jax.random.randint(jax.random.fold_in(k_round, i),
                                  (batch,), 0, size)
        sel = table[take]
        g = jax.grad(lambda q: loss_and_correct(
            q, data_images[sel], data_labels[sel], arch)[0])(p)
        lr_ = jnp.asarray(lr, _dtype(p))
        return jax.tree_util.tree_map(lambda a, b: a - lr_ * b, p, g), g

    n = rows.shape[0]
    return jax.vmap(one)(params, rows, sizes, jnp.arange(n))


@jax.jit
def similarity(params):
    """Eq. 3: ``[n, n]`` mean over leaves of the per-leaf cosine of every
    pair of node models."""
    leaves = jax.tree_util.tree_leaves(params)
    n = leaves[0].shape[0]
    prec = _prec(leaves[0].dtype)
    total = 0.0
    for leaf in leaves:
        x = leaf.reshape(n, -1)
        norms = jnp.sqrt((x * x).sum(axis=1))
        cos = jnp.dot(x, x.T, precision=prec) / jnp.maximum(
            norms[:, None] * norms[None, :], 1e-12)
        total = total + cos.astype(jnp.float32)
    return total / len(leaves)


@jax.jit
def mix(params, edges):
    """Uniform averaging over each node and its in-neighbours."""
    n = edges.shape[0]
    w = edges.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32)
    w = w / w.sum(axis=1, keepdims=True)

    def one(leaf):
        x = leaf.reshape(n, -1)
        out = jnp.dot(w.astype(x.dtype), x, precision=_prec(x.dtype))
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, params)


@functools.partial(jax.jit, static_argnames=("arch",))
def evaluate(params, images, labels, *, arch):
    """Per node (one node at a time): mean test loss and accuracy."""
    def one(p):
        loss, correct = loss_and_correct(p, images, labels, arch)
        return loss.astype(jnp.float32), correct / labels.shape[0]
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

def run(*, seeds, model, traffic, train, parts, test, rounds,
        dtype=jnp.float32):
    """``rounds`` rounds from the seeded start, evaluated after the first
    and the last.  ``seeds`` holds the integer seeds of the weights
    (``init``), the strategy (``strategy``) and the batch draws
    (``stream``).  Returns host arrays: ``p0`` (start), ``p1`` (after
    round 0), ``p_end``, ``grad0`` (the norm of each leaf of round 0's gradients
    over all nodes), ``edges``
    ``[rounds, n, n]``, and ``evals`` = [(losses [n], accuracy [n])] after
    round 0 and after the last round."""
    n = traffic["nodes"]
    k = min(traffic["k"], n - 1)
    net = arch(model)
    init = jax.jit(jax.vmap(functools.partial(init_node, arch=net)))
    p = init(jax.random.split(jax.random.PRNGKey(seeds["init"]), n))
    p0 = jax.device_get(p)
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), p)
    size = max(len(q) for q in parts)
    rows = jnp.asarray(np.stack([np.resize(q, size) for q in parts])
                       .astype(np.int32))
    sizes = jnp.asarray([len(q) for q in parts], jnp.int32)
    images = train.images.astype(dtype)
    test_images = test["images"].astype(dtype)
    stream_key = jax.random.PRNGKey(seeds["stream"])
    strat_key = jax.random.PRNGKey(seeds["strategy"])
    morph = traffic["strategy"] == "morph"
    st = morph_init(strat_key, n) if morph else None
    out = {"p0": p0, "edges": [], "evals": []}
    for rnd in range(rounds):
        p, g = local_step(p, images, train.labels, rows, sizes, stream_key,
                          rnd, arch=net, batch=traffic["batch"],
                          lr=traffic["lr"])
        if morph:
            if rnd % traffic["delta_r"] == 0:
                st = morph_negotiate(st, p, k=k,
                                     view=min(k + 2, n - 1), beta=500.0)
            edges = st.edges
        else:
            edges = epidemic_edges(strat_key, rnd, n=n, k=k)
        p = mix(p, edges)
        out["edges"].append(np.asarray(edges))
        if rnd in (0, rounds - 1):
            losses, acc = evaluate(p, test_images, test["labels"],
                                   arch=net)
            out["evals"].append((np.asarray(losses, np.float64),
                                 np.asarray(acc, np.float64)))
            snap = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32), p)
            out["p1" if rnd == 0 else "p_end"] = snap
        if rnd == 0:
            out["grad0"] = jax.tree_util.tree_map(
                lambda x: float(jnp.linalg.norm(x.astype(jnp.float32))), g)
    out["edges"] = np.stack(out["edges"])
    return out
