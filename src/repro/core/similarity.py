"""Model dissimilarity signals (paper Eq. 3 and Eq. 4).

Morph quantifies peer diversity with the *per-layer* cosine similarity
between two models' parameters, averaged across layers (Eq. 3) so that
large layers do not dominate.  When a node has no direct copy of a peer's
model it falls back to *transitive* estimation from gossiped similarity
reports (Eq. 4), justified by the angular triangle inequality for cosine
similarity (Schubert, SISAP'21).

Two implementations live here:

* pure-jnp functions used everywhere (and as the oracle for the Pallas
  ``pairwise_cosine`` kernel), operating either on pairs of pytrees or on a
  stacked node-axis pytree;
* :class:`SimilarityHistory`, the host-side bounded report store (the
  paper's ``H_z`` of the five most recent reports).
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterable, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The paper keeps the 5 most recent similarity reports per target peer.
HISTORY_DEPTH = 5
_EPS = 1e-12
# Eq. 3 in f32 on every backend: a TPU's default matmul precision rounds
# the operands to bf16, too coarse for cosines of near-equal models.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Eq. 3 — per-layer cosine similarity, averaged across layers.
# ---------------------------------------------------------------------------

def layer_cosine(a: jax.Array, b: jax.Array) -> jax.Array:
    """Cosine similarity between two same-shaped parameter tensors."""
    af = a.reshape(-1).astype(jnp.float32)
    bf = b.reshape(-1).astype(jnp.float32)
    dot = jnp.vdot(af, bf)
    na = jnp.linalg.norm(af)
    nb = jnp.linalg.norm(bf)
    return dot / jnp.maximum(na * nb, _EPS)


def model_similarity(params_a, params_b) -> jax.Array:
    """Eq. 3: mean over layers of per-layer cosine similarity.

    ``params_a`` / ``params_b`` are arbitrary (but matching) pytrees; every
    leaf is treated as one "layer" in the sense of Eq. 3.
    """
    leaves_a = jax.tree_util.tree_leaves(params_a)
    leaves_b = jax.tree_util.tree_leaves(params_b)
    if len(leaves_a) != len(leaves_b):
        raise ValueError(
            f"pytrees disagree: {len(leaves_a)} vs {len(leaves_b)} leaves")
    sims = [layer_cosine(a, b) for a, b in zip(leaves_a, leaves_b)]
    return jnp.mean(jnp.stack(sims))


def pairwise_model_similarity(stacked_params) -> jax.Array:
    """Eq. 3 for *all node pairs at once*.

    ``stacked_params`` is a pytree whose leaves carry a leading node axis
    ``[n, ...]``.  Returns the ``[n, n]`` matrix of layer-averaged cosine
    similarities.  This is the pure-jnp oracle for the Pallas kernel in
    ``repro.kernels``.
    """
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if not leaves:
        raise ValueError("empty pytree")
    n = leaves[0].shape[0]
    acc = jnp.zeros((n, n), jnp.float32)
    for leaf in leaves:
        # Contract over *all* trailing axes without reshaping: a reshape
        # would merge differently-sharded dims and force XLA to all-gather
        # the full (possibly 100B+-param) leaf; tensordot keeps the
        # contraction local per shard + one [n, n] all-reduce.
        lf = leaf.astype(jnp.float32)
        axes = tuple(range(1, lf.ndim))
        dots = jnp.tensordot(lf, lf, axes=(axes, axes),
                             precision=HIGHEST)                # [n, n]
        sq = jnp.einsum(lf, tuple(range(lf.ndim)),
                        lf, tuple(range(lf.ndim)), (0,),
                        precision=HIGHEST)                     # [n]
        norms = jnp.maximum(jnp.sqrt(sq), _EPS)
        acc = acc + dots / (norms[:, None] * norms[None, :])
    return acc / len(leaves)


def dissimilarity(sim: jax.Array) -> jax.Array:
    """Dissimilarity score used for ranking: lower sim == more diverse."""
    return 1.0 - sim


# ---------------------------------------------------------------------------
# Eq. 4 — transitive similarity estimation from gossiped reports.
# ---------------------------------------------------------------------------

@dataclass
class SimilarityReport:
    """One gossiped record: at time ``t``, reporter ``y`` claimed
    ``sim(y, z) = sigma_yz`` about target ``z``."""
    t: int
    reporter: int
    target: int
    sigma: float


@dataclass
class SimilarityHistory:
    """Host-side store of direct + gossiped similarity knowledge at a node.

    ``direct[j]`` is the latest directly measured ``sim(self, j)``;
    ``reports[z]`` is the paper's ``H_z`` — a deque of the
    :data:`HISTORY_DEPTH` most recent third-party reports about ``z``.
    """
    depth: int = HISTORY_DEPTH
    direct: Dict[int, float] = field(default_factory=dict)
    reports: Dict[int, Deque[SimilarityReport]] = field(
        default_factory=lambda: collections.defaultdict(
            lambda: collections.deque(maxlen=HISTORY_DEPTH)))

    def observe_direct(self, peer: int, sim: float) -> None:
        """Record a first-hand Eq.-3 measurement against ``peer``."""
        self.direct[peer] = float(sim)

    def observe_report(self, report: SimilarityReport) -> None:
        """Append a gossiped third-party report to H_z (bounded deque,
        newest-``depth`` kept)."""
        dq = self.reports[report.target]
        if dq.maxlen != self.depth:  # honour a non-default depth
            dq = collections.deque(dq, maxlen=self.depth)
            self.reports[report.target] = dq
        dq.append(report)

    def estimate(self, target: int) -> float | None:
        """Eq. 4: sim^(w_i, w_z) = mean over H_z of sim(w_i, w_y) * sigma_yz.

        Only reports whose reporter ``y`` we know directly contribute (we
        need ``sim(self, y)``).  Returns ``None`` when nothing is known —
        callers treat unknown peers as maximally interesting or skip them,
        per the selection policy.
        """
        if target in self.direct:
            return self.direct[target]
        hz = [r for r in self.reports.get(target, ())
              if r.reporter in self.direct]
        if not hz:
            return None
        vals = [self.direct[r.reporter] * r.sigma for r in hz]
        return float(np.mean(vals))

    def known_peers(self) -> List[int]:
        """Every peer with a direct measurement or at least one report."""
        out = set(self.direct)
        out.update(self.reports)
        return sorted(out)

    def snapshot(self, peers: Iterable[int]) -> Dict[int, float]:
        """Best-effort similarity estimate for each peer in ``peers``."""
        out: Dict[int, float] = {}
        for p in peers:
            est = self.estimate(p)
            if est is not None:
                out[p] = est
        return out


def angular_bound(sim_ij: float, sim_jk: float) -> Tuple[float, float]:
    """Bounds on sim(i,k) implied by the angular triangle inequality.

    arccos is monotone decreasing, so
    ``cos(a_ij + a_jk) <= sim(i,k) <= cos(|a_ij - a_jk|)``.
    Used by property tests to check that transitive estimates are sane.
    """
    a = float(np.arccos(np.clip(sim_ij, -1.0, 1.0)))
    b = float(np.arccos(np.clip(sim_jk, -1.0, 1.0)))
    lo = float(np.cos(min(a + b, np.pi)))
    hi = float(np.cos(abs(a - b)))
    return lo, hi


def node_row(stacked, i: int) -> List[np.ndarray]:
    """Node ``i``'s parameters as a list of flat float64 leaf vectors.

    Shared by the synchronous protocol driver and the netsim transfer
    path so a direct Eq. 3 measurement is bit-identical no matter which
    runtime produced the model copy."""
    if isinstance(stacked, np.ndarray):
        leaves = [stacked]
    else:
        leaves = jax.tree_util.tree_leaves(stacked)
    return [np.asarray(l[i]).astype(np.float64).ravel() for l in leaves]


def pair_similarity_numpy(row_a: List[np.ndarray],
                          row_b: List[np.ndarray]) -> float:
    """Eq. 3 between two single-node rows from :func:`node_row`."""
    if len(row_a) != len(row_b):
        raise ValueError("rows disagree on leaf count")
    acc = 0.0
    for a, b in zip(row_a, row_b):
        na = max(float(np.linalg.norm(a)), _EPS)
        nb = max(float(np.linalg.norm(b)), _EPS)
        acc += float(a @ b) / (na * nb)
    return acc / len(row_a)


def similarity_matrix_numpy(stacked: Mapping[str, np.ndarray] | np.ndarray,
                            ) -> np.ndarray:
    """Numpy twin of :func:`pairwise_model_similarity` for the host-side
    protocol simulator (keeps the simulator free of device transfers)."""
    if isinstance(stacked, np.ndarray):
        leaves = [stacked]
    else:
        leaves = [np.asarray(v)
                  for v in jax.tree_util.tree_leaves(stacked)]
    if not leaves:
        raise ValueError("empty pytree")
    n = leaves[0].shape[0]
    acc = np.zeros((n, n), np.float64)
    for leaf in leaves:
        flat = leaf.reshape(n, -1).astype(np.float64)
        dots = flat @ flat.T
        norms = np.maximum(np.linalg.norm(flat, axis=-1), _EPS)
        acc += dots / (norms[:, None] * norms[None, :])
    return acc / len(leaves)
