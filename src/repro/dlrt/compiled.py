"""Compiled superstep engine: whole Morph rounds fused into ``lax.scan``.

The host runner (:class:`repro.dlrt.DecentralizedRunner`) syncs to the
host every round — strategy on host, mixing on device — so large sweeps
are dominated by dispatch and ``device_get`` overhead rather than the MXU
kernels.  This engine runs **K rounds in one jitted program**:

  scan step r:  vmapped local SGD
                -> similarity cache refresh      [lax.cond, sim_every]
                -> strategy.graph_round          [lax.cond, delta_r]
                -> row-stochastic mixing         [apply_mixing or the
                                                  fused Pallas kernel]

with the strategy state (:class:`repro.core.MorphGraphState` for Morph, a
PRNG key for Epidemic, ``()`` for the static baselines) carried through
the scan and **zero host round-trips inside a chunk**.  Per-round in-edge
matrices come back as one stacked ``[K, n, n]`` bool array (the only scan
output) and are decoded on exit into ``edge_history`` / comm-bytes /
:class:`RoundRecord` entries — the same ``MetricsLog`` the host runner
produces.

Chunking: evaluation rounds (``eval_every`` cadence plus the final round)
form the chunk boundaries, so the engine evaluates exactly where the host
runner does and the two paths emit identical logs.  See DESIGN.md §7 for
the layout and for when the host path is still required (protocol-level
message-faithful runs, netsim).

**Sharded mode** (DESIGN.md §8).  Pass ``mesh`` (see
:func:`repro.launch.mesh.make_superstep_mesh`) and the whole superstep
runs under ``shard_map`` with the **node axis as a mesh axis**: each
device owns ``n_pad / num_devices`` nodes' parameters, optimizer state
and batches, the vmapped local step runs data-parallel, and the
cross-node operations lower to real collectives —

* similarity needs every pair, so the post-step parameters are
  ``all_gather``-ed along the node axis before the Eq.-3 kernel;
* ``graph_mix`` becomes either each device's **row block** of ``W``
  applied to the gathered population (``collective="gather"``, bitwise
  identical to the single-device contraction) or a partial-products
  ``psum`` along the node axis (``collective="psum"``, reduce-scatter
  schedule, f32-rounding-close);
* the strategy's graph state, the ``[n, n]`` similarity cache and
  ``graph_round`` itself stay **replicated** — every device runs the
  identical (deterministic) negotiation, which is what lets the edge
  stack come back from the scan as a replicated output.

The node axis is zero-padded up to a multiple of the shard count
(``n_pad``); padded rows carry edge-replicated parameters, never gain
in-edges (``W`` is embedded with an identity tail), and are sliced away
from every externally visible array — ``params`` / ``opt_state`` are
properties returning the logical ``[n, ...]`` view.

**Batch streaming.**  By default each chunk prefetches its ``[K, n, b,
...]`` batch stack from the host batcher.  Pass ``data_stream``
(:class:`repro.data.DeviceDataStream`) instead to keep the dataset
device-resident once (shared ``[N_total, ...]`` arrays plus per-node
``[n, S]`` index tables; under sharding the dataset is replicated and
only the tables are node-sharded) and draw every round's batch inside
the scan body with ``jax.random`` — no host transfer per round at all.

**Dense network model** (DESIGN.md §9).  Pass ``net``
(:class:`repro.netsim.DenseNetwork`, surfaced as ``RunnerConfig.net``)
and the scan body prices the network *inside the fused program*: the
carry grows a ring buffer of the last ``S`` post-step parameter
snapshots (plus the matching last-step-round ring), per-edge delays
(keyed jitter + model serialization) quantize to round-staleness
indices into that buffer, Bernoulli/partition/liveness losses remove
edges from delivery (weights renormalize into self — exactly the
event-driven runner's per-arrival mixing), and churned-out or
straggling nodes skip their local step on the rounds the shared fault
timeline says they are down or mid-computation.  Per-round outputs
extend to ``(edges, delivered, staleness histogram, staleness sum)``,
decoded into ``net_stats`` / ``delivered_history`` at chunk exit.
Under ``profiles.ideal()`` with no faults the ring has depth 1 and the
whole path reduces to the vanilla engine bit-for-bit (conformance:
tests/test_dense_net.py).  Sharded mode gathers the snapshot ring
along the node axis exactly like the parameters (``collective="gather"``
only).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compress import (CompressConfig, decode_wire_tree,
                        encode_delta_payload, wire_bytes_tree,
                        zero_residual)
from ..core import apply_mixing, apply_mixing_compressed
from ..core.mixing import (apply_consensus_correction, tensordot_mix_leaf,
                           uniform_weights_jax)
from ..data.pipeline import DeviceDataStream, StackedBatcher
from ..kernels import ops
from ..optim import Optimizer
from ..sparse.adjacency import (SparseAdjacency, dense_to_csr,
                                pad_adjacency)
from ..sparse.mix import sparse_mix_pytree
from .metrics import MetricsLog, RoundRecord
from .runtime import (RunnerConfig, make_evaluator, make_local_step,
                      make_round_record, net_staleness_mean,
                      stacked_model_bytes)

COLLECTIVES = ("gather", "psum")
ENGINES = ("dense", "sparse")
SPARSE_MIX_MODES = ("exact", "gather")
# Above this population the sparse engine stops decoding dense [n, n]
# edge matrices into edge_history and appends compact (idx, mask) pairs.
SPARSE_EDGE_DECODE_MAX = 4096


def eval_boundaries(rounds: int, eval_every: int) -> List[Tuple[int, int]]:
    """Inclusive ``(start, end)`` chunks whose ends are exactly the rounds
    after which the host runner evaluates."""
    ends = sorted({r for r in range(rounds) if r % eval_every == 0}
                  | {rounds - 1})
    chunks, start = [], 0
    for e in ends:
        chunks.append((start, e))
        start = e + 1
    return chunks


def _pad_nodes(tree, n_pad: int):
    """Edge-replicate the leading node axis of every leaf up to ``n_pad``
    (repeating the last real node keeps padded rows numerically
    well-behaved for arbitrary loss functions, unlike zeros)."""
    def one(x):
        if getattr(x, "ndim", 0) == 0:
            return jnp.asarray(x)        # shared scalar (opt counter etc.)
        pad = n_pad - x.shape[0]
        if pad <= 0:
            return jnp.asarray(x)
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(jnp.asarray(x), width, mode="edge")
    return jax.tree_util.tree_map(one, tree)


# ---------------------------------------------------------------------------
# Dense-network scan helpers (DESIGN.md §9), shared by this engine's
# round bodies and the sweep engine's vmapped per-experiment body
# (dlrt.sweep, DESIGN.md §14).  Pure functions of their arguments —
# everything an engine would close over (n, S, the uniform-mixing flag)
# arrives explicitly.
# ---------------------------------------------------------------------------

def net_select(mask, new, old):
    """Per-node where over a state pytree; scalar leaves (shared
    optimizer counters) and leaves not on the node axis always
    advance."""
    def one(a, b):
        if getattr(a, "ndim", 0) == 0 or a.shape[0] != mask.shape[0]:
            return a
        m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree_util.tree_map(one, new, old)


def net_effective(edges, w, up, step, stal, drop, S: int, *,
                  uniform: bool):
    """Delivery + mixing plan at logical n: which negotiated edges
    arrive, the renormalized weights over the arrived set, the
    ``[n, n, S]`` staleness-expanded weights and the per-round
    staleness stats."""
    n = edges.shape[0]
    eye = jnp.eye(n, dtype=bool)
    active = up & step                   # receivers that mix
    delivered = edges & ~drop & up[None, :] & active[:, None]
    if uniform:
        # Alg. 2 l.12 over the models that actually arrived —
        # the same renormalization AsyncRunner._mix_one applies.
        w_eff = uniform_weights_jax(delivered)
    else:
        support = delivered | eye
        kept = w.astype(jnp.float32) * support
        lost = (w.astype(jnp.float32) * ~support).sum(axis=1)
        w_eff = kept + jnp.diag(lost)
    w_eff = jnp.where(active[:, None], w_eff,
                      jnp.eye(n, dtype=w_eff.dtype))
    d_idx = jnp.where(eye, 0, stal)
    onehot = d_idx[:, :, None] == jnp.arange(S)[None, None, :]
    w_stal = w_eff[:, :, None] * onehot              # [n, n, S]
    stale_counts = jnp.sum(onehot & delivered[:, :, None],
                           axis=(0, 1)).astype(jnp.int32)
    return delivered, d_idx, w_stal, stale_counts


def net_push(params, netstate, rnd, step, S: int):
    """Advance both rings: slot 0 becomes this round's post-step
    snapshot / last-step round."""
    hist, lhist = netstate
    def one(h, p):
        if S == 1:
            return p[:, None]
        return jnp.concatenate([p[:, None], h[:, :-1]], axis=1)
    hist = jax.tree_util.tree_map(one, hist, params)
    last = jnp.where(step, rnd.astype(jnp.int32), lhist[:, 0])
    lhist = last[:, None] if S == 1 else \
        jnp.concatenate([last[:, None], lhist[:, :-1]], axis=1)
    return hist, lhist


def net_observed(rnd, lhist, d_idx, delivered):
    """Sum over delivered edges of the *content* staleness: this
    round minus the sender's last completed step as of the
    snapshot each edge delivers from."""
    n = d_idx.shape[0]
    sender = jnp.broadcast_to(jnp.arange(n)[None, :], (n, n))
    last = lhist[sender, d_idx]                      # [n, n]
    obs = rnd.astype(jnp.int32) - last
    return jnp.sum(jnp.where(delivered, obs, 0)).astype(jnp.int32)


class CompiledSuperstep:
    """Runs an in-graph-capable :class:`TopologyStrategy` (one exposing
    ``init_graph_state`` / ``graph_round`` — the contract in
    ``core.baselines``) in fused K-round supersteps.

    Construction arguments (shapes: ``n`` = ``cfg.n_nodes`` logical
    nodes, node-stacked pytrees carry a leading ``[n, ...]`` axis):

    * ``loss_fn(params, batch) -> (loss, aux)`` / ``eval_fn`` — per-node
      functions, vmapped by the engine;
    * ``batcher`` — host batcher yielding ``[n, b, ...]`` stacks
      (prefetched per chunk), or ``None`` with ``data_stream`` set;
    * ``data_stream`` — :class:`repro.data.DeviceDataStream` for
      device-resident in-scan batch drawing (mutually exclusive with
      ``batcher``);
    * ``mesh`` — optional 1-D ``("data",)`` JAX mesh
      (:func:`repro.launch.mesh.make_superstep_mesh`); shards the node
      axis via ``shard_map``;
    * ``collective`` — sharded mixing schedule, ``"gather"`` (row-block,
      bitwise-matches single-device) or ``"psum"`` (partial-products
      reduce);
    * ``use_pallas`` routes similarity through the blocked Gram kernel
      and mixing through the fused kernels (``interpret=True`` to
      execute their bodies on CPU); the default pure-jnp path is what
      the conformance tests pit against the host loop bit-for-bit;
    * ``net`` — optional :class:`repro.netsim.DenseNetwork`: price
      latency/staleness/drops/churn inside the scan (module docstring;
      requires ``collective="gather"`` when sharded);
    * ``chunk`` — cap on rounds fused per compiled dispatch (None =
      one superstep per eval chunk).  Trajectory-invariant; this and
      ``block_d``/``collective`` must arrive concrete — ``"auto"``
      sentinels are resolved upstream by ``repro.tune`` (DESIGN.md
      §10);
    * ``engine`` — ``"dense"`` (the original path) or ``"sparse"``
      (DESIGN.md §11).  Sparse-native strategies (``sparse = True``,
      e.g. :class:`repro.sparse.SparseMorphStrategy`) carry CSR
      ``[n, k]`` adjacency through the scan, mix in O(n·k·D) and emit
      ``(idx, mask)`` stacks instead of ``[K, n, n]`` edges; dense
      strategies under ``engine="sparse"`` run in **compat mode**,
      governed by ``sparse_mix``;
    * ``sparse_mix`` — compat-mode numerics: ``"exact"`` mixes through
      the identical dense contraction (bitwise vs the dense engine —
      the conformance anchor), ``"gather"`` converts each round's
      ``(edges, w)`` to CSR in-scan and mixes through the sparse
      gather path (parity to tolerance);
    * ``mix_chunk_d`` — chunked per-layer exchange (DESIGN.md §12):
      every mixing contraction (dense tensordot, sharded row-block and
      psum schedules, the net-mode staleness contraction, the sparse
      gather) processes at most this many flattened feature elements
      per step, so the f32-upcast / neighbor-gather buffers stay
      ``O(n · mix_chunk_d)`` instead of ``O(n · leaf_size)`` — the knob
      that lets multi-MB CNN layers through the engines.  Contraction
      axes are never split: dense tensordot paths are invariant to the
      chunking up to the last ulp of XLA's shape-chosen dot kernel;
      the sparse gather path is last-ulp allclose with identical edge
      sequences (XLA fuses the self-term add shape-dependently).
      Pallas paths do their own blocking and ignore it;
    * ``eval_batch_chunk`` — evaluate the shared test set at most this
      many samples per vmapped forward pass, combining chunk means by
      sample-count weights (bounds the ``[n, b_test, ...]`` activation
      footprint; f32-rounding-close, not bitwise, across different
      chunkings).

    Invariants: ``params`` / ``opt_state`` expose the logical ``[n,
    ...]`` view even in sharded mode (padding is internal); the decoded
    ``MetricsLog`` / ``edge_history`` / comm-byte accounting are
    identical to the host runner's for the same trajectory.
    """

    def __init__(self, *, init_fn: Callable, loss_fn: Callable,
                 eval_fn: Callable, optimizer: Optimizer,
                 batcher: Optional[StackedBatcher],
                 test_batch: Dict[str, np.ndarray],
                 strategy, cfg: RunnerConfig,
                 use_pallas: bool = False, interpret: bool = False,
                 block_d: Optional[int] = None,
                 params=None, opt_state=None,
                 mesh=None, collective: str = "gather",
                 data_stream: Optional[DeviceDataStream] = None,
                 net=None, chunk: Optional[int] = None,
                 engine: str = "dense", sparse_mix: str = "exact",
                 mix_chunk_d: Optional[int] = None,
                 eval_batch_chunk: Optional[int] = None,
                 compress: Optional[CompressConfig] = None):
        if isinstance(block_d, str) or isinstance(chunk, str) \
                or isinstance(mix_chunk_d, str) \
                or isinstance(eval_batch_chunk, str) or engine == "auto" \
                or isinstance(compress, str):
            raise TypeError(
                "the engine takes concrete knobs; \"auto\" sentinels are "
                "resolved by DecentralizedRunner via repro.tune."
                "resolve_knobs (and compress specs parsed to "
                "CompressConfig) before the engine is built")
        # A disabled codec is exactly compress=None: no residual in the
        # carry, no codec ops traced, bitwise-identical HLO — the
        # conformance matrices pin this.
        codec = compress if compress is not None and compress.enabled \
            else None
        if codec is not None and use_pallas:
            raise ValueError(
                "compressed gossip runs on the XLA mixing/similarity "
                "paths; use_pallas=True is not supported with "
                "compress != 'none' (the Pallas kernels read raw "
                "params)")
        if not getattr(strategy, "in_graph", False):
            raise TypeError(
                f"strategy {getattr(strategy, 'name', strategy)!r} has no "
                "in-graph surface (init_graph_state/graph_round); use the "
                "host DecentralizedRunner for protocol-level strategies")
        if collective not in COLLECTIVES:
            raise ValueError(f"collective={collective!r} not in "
                             f"{COLLECTIVES}")
        if engine not in ENGINES:
            raise ValueError(f"engine={engine!r} not in {ENGINES}")
        if sparse_mix not in SPARSE_MIX_MODES:
            raise ValueError(f"sparse_mix={sparse_mix!r} not in "
                             f"{SPARSE_MIX_MODES}")
        sparse_native = bool(getattr(strategy, "sparse", False))
        if sparse_native and engine != "sparse":
            raise TypeError(
                f"strategy {getattr(strategy, 'name', strategy)!r} returns "
                "CSR adjacency (sparse=True); select it with "
                "RunnerConfig.engine='sparse'")
        if engine == "sparse" and net is not None:
            raise ValueError(
                "the sparse engine does not support the dense in-scan "
                "network model yet (ROADMAP: compressed/priced gossip); "
                "use engine='dense' with cfg.net")
        if engine == "sparse" and not sparse_native \
                and sparse_mix == "gather" and mesh is not None:
            raise ValueError(
                "compat gather-mix (dense strategy through in-scan CSR "
                "conversion) is a single-device numerics path; sharded "
                "runs use sparse_mix='exact' or a sparse-native strategy")
        if codec is not None and mesh is not None and not codec.sim:
            raise ValueError(
                "the sharded schedules move only the compressed wire "
                "along the node axis, so control/similarity traffic "
                "necessarily reads the decoded payload; "
                "CompressConfig(sim=False) is a single-device knob")
        if data_stream is None and batcher is None:
            raise ValueError("need a host batcher or a data_stream")
        if net is not None and mesh is not None and collective != "gather":
            raise ValueError("the dense network model gathers its "
                             "snapshot ring along the node axis; use "
                             "collective='gather' (got "
                             f"{collective!r})")
        if data_stream is not None and data_stream.n != cfg.n_nodes:
            raise ValueError(f"data_stream covers {data_stream.n} nodes, "
                             f"config says {cfg.n_nodes}")
        self.cfg = cfg
        self.strategy = strategy
        self.engine = engine
        self.mix_chunk_d = mix_chunk_d
        self.eval_batch_chunk = eval_batch_chunk
        self.sparse_native = sparse_native
        self.sparse_mix = sparse_mix
        self._last_isolated: Optional[int] = None
        self.batcher = batcher
        self.stream = data_stream
        # superstep-length cap (rounds per scan): eval chunks longer than
        # this are subdivided — evaluation cadence is unchanged, only how
        # many rounds each compiled dispatch fuses.  None = one superstep
        # per eval chunk (the pre-tuner behaviour).
        self.chunk = chunk
        self.test_batch = {k: jnp.asarray(v) for k, v in test_batch.items()}
        if params is None:
            keys = jax.random.split(jax.random.PRNGKey(cfg.seed),
                                    cfg.n_nodes)
            params = jax.vmap(init_fn)(keys)
            opt_state = jax.vmap(optimizer.init)(params)
        self.opt = optimizer
        self.log = MetricsLog()
        self.edge_history: list = []
        self._comm_bytes = 0
        self._model_bytes = cfg.model_bytes \
            or stacked_model_bytes(params, cfg.n_nodes)
        # What one transfer costs on the wire: the codec's analytic byte
        # count (DESIGN.md §13) — comm accounting and the dense network
        # model's serialization delay both price this, not the dense
        # f32 payload.
        self.codec = codec
        self._wire_bytes = self._model_bytes if codec is None \
            else wire_bytes_tree(params, cfg.n_nodes, codec)

        # --- node-axis sharding layout -------------------------------------
        n = cfg.n_nodes
        self.mesh = mesh
        self.collective = collective
        if mesh is not None:
            from .distributed import superstep_node_sharding
            self._axes, self._shard, self._nspec = \
                superstep_node_sharding(mesh)
        else:
            self._axes, self._shard, self._nspec = (), 1, None
        self.n_pad = math.ceil(n / self._shard) * self._shard
        self._n_local = self.n_pad // self._shard

        self._params = _pad_nodes(params, self.n_pad)
        self._opt_state = _pad_nodes(opt_state, self.n_pad)
        if mesh is not None:
            put = lambda t: jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, self._leaf_pspec(x))), t)
            self._params = put(self._params)
            self._opt_state = put(self._opt_state)

        # --- dense network model layout (DESIGN.md §9) ---------------------
        self.net = net
        self.net_stats: Optional[Dict] = None
        self.delivered_history: list = []
        if net is not None:
            # Latency quantization prices the *wire* payload: a
            # compressed transfer serializes faster, so the ring can be
            # shallower than the uncompressed run's.
            S = net.depth(self._wire_bytes)
            up_np, step_np = net.round_masks(cfg.rounds, n)
            self._net_S = S
            self._net_up = jnp.asarray(up_np)        # [rounds, n] bool
            self._net_step = jnp.asarray(step_np)    # [rounds, n] bool
            # snapshot ring: leaf [n_pad, S, ...] — slot d holds the
            # post-step params from d rounds back (seeded with the
            # initial models); lhist [n, S] mirrors each node's
            # last-completed-step round (-1 = never stepped).  Under
            # compression the ring holds the dense f32 **reconstructed
            # replicas** (what peers hold after decoding every
            # transmitted delta, DESIGN.md §13): slot s is hat_j as of
            # s rounds back — on a reliable in-order transport that is
            # exactly what a receiver of that stale payload has
            # integrated, and slot 0 doubles as the replica the next
            # round's delta is coded against.  Only the analytic wire
            # bytes stay compressed (serialization delay + comm
            # accounting); ring memory is dense f32.
            if codec is None:
                snap0 = self._params
            else:
                snap0 = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), self._params)
            hist = jax.tree_util.tree_map(
                lambda x: jnp.repeat(x[:, None], S, axis=1), snap0)
            lhist = jnp.full((n, S), -1, jnp.int32)
            if mesh is not None:
                hist = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, NamedSharding(mesh, P(self._nspec))), hist)
                lhist = jax.device_put(lhist, NamedSharding(mesh, P()))
            self._netstate = (hist, lhist)
            self.net_stats = {"delivered": 0, "dropped": 0,
                              "staleness_hist": np.zeros(S, np.int64),
                              "staleness_sum": 0}
        else:
            self._net_S = 0
            self._netstate = ()

        # Error-feedback residual (DESIGN.md §13): f32 zeros shaped like
        # the padded params, carried through the scan.  () when the
        # codec is off — an empty pytree adds nothing to the carry, so
        # the uncompressed program is structurally unchanged.
        #
        # hat: the CHOCO-SGD-style reconstructed replica.  Every node
        # transmits ``encode((params - hat) + resid)`` and *everyone*
        # (sender included) advances ``hat += decode(wire)``, so hat_i
        # is bit-for-bit what each peer holds as node i's model and
        # mixing contracts over these dense f32 replicas.  Coding the
        # *difference* is what makes top-k trainable: an untransmitted
        # coordinate leaves the replica (and, through the consensus
        # correction, the local model) untouched instead of mixing in a
        # zero, and the quantization scale tracks the SGD-step-sized
        # delta rather than the weights themselves.  Seeded with the
        # shared initial params (f32), like the residual it is () when
        # the codec is off; in net mode the snapshot ring's slot 0 *is*
        # the replica, so no separate hat is carried there either.
        # Sharding: gather mode keeps hat replicated at full n_pad
        # (receivers rebuild the whole decoded population as
        # ``hat + decode(gathered wire)``, which becomes the next hat);
        # psum mode only ever needs the local rows, so hat shards with
        # the params.
        if codec is None:
            self._resid = ()
            self._hat = ()
        else:
            resid = zero_residual(self._params)
            hat = () if net is not None else jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), self._params)
            if mesh is not None:
                resid = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, NamedSharding(mesh, self._leaf_pspec(x))),
                    resid)
                hat_spec = (lambda x: P()) if collective == "gather" \
                    else self._leaf_pspec
                hat = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, NamedSharding(mesh, hat_spec(x))), hat)
            self._resid = resid
            self._hat = hat

        self.gstate = strategy.init_graph_state()
        # Sparse-native strategies never consume the [n, n] similarity
        # cache; carry an empty placeholder so the scan state stays
        # O(n·k) at paper-scale n.
        self.sim = jnp.zeros((0, 0), jnp.float32) if sparse_native \
            else jnp.zeros((n, n), jnp.float32)
        needs_sim = bool(getattr(strategy, "needs_sim", False))
        needs_params = bool(getattr(strategy, "needs_params", False))
        # Cadence at which a sparse control plane actually reads params
        # (SparseMorphStrategy re-negotiates every delta_r rounds) — the
        # sharded psum schedule gates its params gather on it.
        ctrl_every = int(getattr(strategy, "delta_r", 1) or 1)
        uniform = bool(getattr(strategy, "uniform_mixing", False))
        if not needs_sim:
            sim_fn = None
        elif use_pallas:
            sim_fn = lambda p: ops.model_pairwise_cosine(
                p, block_d=block_d, interpret=interpret)
        else:
            sim_fn = strategy.compute_sim

        local_step = make_local_step(loss_fn, optimizer)
        n_pad, n_local, axes = self.n_pad, self._n_local, self._axes
        sharded = mesh is not None
        stream = data_stream

        def embed_w(w):
            # [n, n] -> [n_pad, n_pad]: identity tail, so padded rows keep
            # their own (dummy) model and never leak into real rows.
            if n_pad == n:
                return w
            wp = jnp.zeros((n_pad, n_pad), w.dtype).at[:n, :n].set(w)
            tail = jnp.arange(n, n_pad)
            return wp.at[tail, tail].set(1)

        def shard_index():
            idx = jnp.int32(0)
            for a in axes:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            return idx

        def gather_full(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, axes, axis=0, tiled=True),
                tree)

        def mix_rows(w_rows, full):
            # row block of W @ X — same per-element dot products as
            # apply_mixing, so bitwise-identical to the unsharded engine.
            if use_pallas:
                return ops.mix_pytree(w_rows.astype(jnp.float32), full,
                                      block_d=block_d, interpret=interpret)
            return jax.tree_util.tree_map(
                lambda leaf: tensordot_mix_leaf(w_rows, leaf, mix_chunk_d),
                full)

        def mix_psum(w_cols, local):
            # each device contributes W[:, its cols] @ X[its rows]; the
            # psum is the node-axis reduction (reduce-scatter schedule).
            def one(leaf):
                if use_pallas:
                    flat = leaf.reshape(n_local, -1).astype(jnp.float32)
                    part = ops.mix(w_cols.astype(jnp.float32), flat,
                                   block_d=block_d, interpret=interpret)
                    part = part.reshape((n_pad,) + leaf.shape[1:])
                else:
                    # f32 partial products — the psum reduces before the
                    # final downcast, so cast_back is deferred.
                    part = tensordot_mix_leaf(w_cols, leaf, mix_chunk_d,
                                              cast_back=False)
                summed = jax.lax.psum(part, axes)
                own = jax.lax.dynamic_slice_in_dim(
                    summed, shard_index() * n_local, n_local, 0)
                return own.astype(leaf.dtype)
            return jax.tree_util.tree_map(one, local)

        def _sparse_mix(adj, tree, rows=None):
            # k-sparse gather mixing; the Pallas block-sparse kernel is
            # single-device-layout only (rows=None), the jnp gather path
            # covers the sharded row-block case.
            if use_pallas and rows is None:
                return ops.mix_sparse_pytree(
                    adj.idx, adj.w, adj.w_self, tree, mask=adj.mask,
                    block_d=block_d, interpret=interpret)
            return sparse_mix_pytree(adj, tree, rows=rows,
                                     chunk_d=mix_chunk_d)

        # Compat mode (engine="sparse" with a dense-returning strategy)
        # converts each round's (edges, w) in-scan; n-1 slots make the
        # conversion lossless for any in-degree, so this is a numerics
        # path (sparse_mix="gather" parity), not the scaling path.
        compat_k = max(1, n - 1)

        def refresh_sim(rnd, params_logical, sim):
            return jax.lax.cond(
                rnd % cfg.sim_every == 0,
                lambda p, s: sim_fn(p).astype(jnp.float32),
                lambda p, s: s,
                params_logical, sim)

        # --- compressed-gossip scan helpers (codec is not None only) -------
        # comp(): one difference-coded error-feedback step over a
        # node-stacked tree.  The wire carries ``encode((params - hat)
        # + resid)`` and the returned ``decoded = hat + decode(wire)``
        # is the advanced replica — what every peer now holds as these
        # rows' models (and the next round's hat).  The residual only
        # accumulates transmitted coordinates' quantization error;
        # dropped top-k coordinates persist in the replica gap (see
        # encode_delta_payload).  All ops are row-wise, so sharded row
        # blocks encode/decode bitwise like the same rows on one
        # device; decode_rows() turns a (gathered) wire back into dense
        # f32 *delta* rows, to be added onto the matching hat rows.
        def comp(params_tree, hat_tree, resid_tree):
            delta = jax.tree_util.tree_map(
                lambda p, h: p.astype(jnp.float32) - h,
                params_tree, hat_tree)
            wire, dec, new_resid = encode_delta_payload(delta, resid_tree,
                                                        codec)
            decoded = jax.tree_util.tree_map(jnp.add, hat_tree, dec)
            return wire, decoded, new_resid

        def decode_rows(wire_tree, template_tree):
            return decode_wire_tree(wire_tree, template_tree, codec)

        # Consensus step size (CHOCO's γ) — trace-time constant; 1.0 for
        # dense codecs keeps the full correction bitwise, < 1 damps the
        # replica-difference step under aggressive top-k.
        gam = codec.consensus_gamma if codec is not None else 1.0

        def slice_rows(tree, off):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, off, n_local,
                                                       0), tree)

        # --- dense-network scan helpers (net is not None only) -------------
        # The per-round delivery/ring machinery (net_select /
        # net_effective / net_push / net_observed) lives at module level
        # so the sweep engine's vmapped body reuses it verbatim; only
        # the profile-draw plumbing (net_masks) and the mixing
        # contraction (net_mix, kernel-path-aware) stay engine-local.
        S = self._net_S
        model_bytes = self._wire_bytes

        def net_masks(rnd):
            r = jnp.minimum(rnd, cfg.rounds - 1)
            up, step = self._net_up[r], self._net_step[r]      # [n] bool
            stal = net.staleness_matrix(rnd, n, model_bytes, S)
            drop = net.drop_mask(rnd, n)
            return up, step, stal, drop

        def net_mix(w_stal_flat, hist):
            """``[m, n_h * S] @ [n_h * S, ...]`` — the staleness-expanded
            contraction, same f32/HIGHEST schedule as ``apply_mixing`` so
            a depth-1 ring is bitwise the vanilla mix."""
            flat = jax.tree_util.tree_map(
                lambda l: l.reshape((l.shape[0] * l.shape[1],)
                                    + l.shape[2:]), hist)
            if use_pallas:
                return ops.mix_pytree(w_stal_flat, flat, block_d=block_d,
                                      interpret=interpret)
            return jax.tree_util.tree_map(
                lambda leaf: tensordot_mix_leaf(w_stal_flat, leaf,
                                                mix_chunk_d),
                flat)

        def round_body(carry, xs):
            # Single-device body: identical to the pre-sharding engine.
            params, opt_state, gstate, sim, netstate, resid, hat = carry
            rnd, batch = xs
            new_p, new_o = local_step(params, opt_state, batch)
            if net is None:
                params, opt_state = new_p, new_o
            else:
                up, step, stal, drop = net_masks(rnd)
                params = net_select(step, new_p, params)
                opt_state = net_select(step, new_o, opt_state)
            if codec is not None:
                # One codec step per round: what every peer (and, with
                # codec.sim, the Eq.-3 control plane) sees this round is
                # the advanced replica hat + decode(wire), never the raw
                # params.  In net mode the ring's slot 0 (last round's
                # push) is the replica the delta is coded against.
                hat_prev = hat if net is None else \
                    jax.tree_util.tree_map(lambda x: x[:, 0], netstate[0])
                wire, decoded, resid = comp(params, hat_prev, resid)
                if net is None:
                    hat = decoded
            if sim_fn is not None:
                sim_src = decoded if codec is not None and codec.sim \
                    else params
                sim = refresh_sim(rnd, sim_src, sim)
            gstate, edges, w = strategy.graph_round(gstate, rnd, sim)
            if net is None:
                if codec is not None:
                    if engine == "sparse" and sparse_mix == "gather":
                        adj = dense_to_csr(edges, w.astype(jnp.float32),
                                           compat_k)
                        params = apply_consensus_correction(
                            _sparse_mix(adj, decoded), params, decoded,
                            gamma=gam)
                    else:
                        params = apply_mixing_compressed(
                            w.astype(jnp.float32), params, decoded,
                            chunk_d=mix_chunk_d, gamma=gam)
                elif engine == "sparse" and sparse_mix == "gather":
                    # Compat numerics path: convert the dense round
                    # output to CSR in-scan and mix through the sparse
                    # gather contraction (parity-tested vs the dense
                    # engine to tolerance; "exact" mode below is the
                    # bitwise path).
                    adj = dense_to_csr(edges, w.astype(jnp.float32),
                                       compat_k)
                    params = _sparse_mix(adj, params)
                elif use_pallas and uniform:
                    params = ops.mix_masked_pytree(edges, params,
                                                   block_d=block_d,
                                                   interpret=interpret)
                elif use_pallas:
                    params = ops.mix_pytree(w.astype(jnp.float32), params,
                                            block_d=block_d,
                                            interpret=interpret)
                else:
                    params = apply_mixing(w.astype(jnp.float32), params,
                                          chunk_d=mix_chunk_d)
                return (params, opt_state, gstate, sim, netstate,
                        resid, hat), edges
            netstate = net_push(decoded if codec is not None else params,
                                netstate, rnd, step, S)
            delivered, d_idx, w_stal, stale_counts = net_effective(
                edges, w, up, step, stal, drop, S, uniform=uniform)
            obs_sum = net_observed(rnd, netstate[1], d_idx, delivered)
            if codec is None:
                params = net_mix(w_stal.reshape(n, n * S), netstate[0])
            else:
                # The ring holds the dense f32 replicas; the same
                # staleness-expanded contraction runs over them, then
                # the consensus-difference correction against this
                # round's own replica (slot 0 after the push).
                mixed = net_mix(w_stal.reshape(n, n * S), netstate[0])
                params = apply_consensus_correction(mixed, params,
                                                    decoded, gamma=gam)
            return (params, opt_state, gstate, sim, netstate, resid,
                    hat), (edges, delivered, stale_counts, obs_sum)

        def pad_mask(m):
            # logical [n] bool -> [n_pad] (padded rows behave like the
            # vanilla engine: they step every round, receive nothing).
            if n_pad == n:
                return m
            return jnp.concatenate([m, jnp.ones((n_pad - n,), bool)])

        def embed_w_stal(w_stal):
            # [n, n, S] -> [n_pad, n_pad * S]: identity tail at staleness
            # 0, so padded rows keep their own fresh (dummy) snapshot.
            if n_pad == n:
                return w_stal.reshape(n, n * S)
            wp = jnp.zeros((n_pad, n_pad, S),
                           w_stal.dtype).at[:n, :n, :].set(w_stal)
            tail = jnp.arange(n, n_pad)
            wp = wp.at[tail, tail, 0].set(1.0)
            return wp.reshape(n_pad, n_pad * S)

        def round_body_sharded_net(carry, xs):
            # Per-device net body: the snapshot ring is node-sharded like
            # the params and all_gathered once per round — its slot 0 is
            # this round's post-step population, so the Eq.-3 refresh
            # reads it instead of a second params gather.  Under the
            # codec the ring carries the dense f32 replicas, so the
            # gather moves dense snapshots either way (the codec's
            # traffic claim lives in the analytic wire bytes that price
            # delay and comm accounting, not in this schedule's
            # collective — documented in DESIGN.md §13).
            params, opt_state, gstate, sim, netstate, resid, hat = carry
            rnd, batch = xs
            new_p, new_o = local_step(params, opt_state, batch)
            up, step, stal, drop = net_masks(rnd)
            step_local = jax.lax.dynamic_slice_in_dim(
                pad_mask(step), shard_index() * n_local, n_local, 0)
            params = net_select(step_local, new_p, params)
            opt_state = net_select(step_local, new_o, opt_state)
            if codec is not None:
                # Local rows' replica = ring slot 0 before the push.
                hat_prev = jax.tree_util.tree_map(lambda x: x[:, 0],
                                                  netstate[0])
                wire, decoded, resid = comp(params, hat_prev, resid)
            netstate = net_push(decoded if codec is not None else params,
                                netstate, rnd, step, S)
            hist_full = gather_full(netstate[0])
            if sim_fn is not None:
                logical = jax.tree_util.tree_map(lambda x: x[:n, 0],
                                                 hist_full)
                sim = refresh_sim(rnd, logical, sim)
            gstate, edges, w = strategy.graph_round(gstate, rnd, sim)
            delivered, d_idx, w_stal, stale_counts = net_effective(
                edges, w, up, step, stal, drop, S, uniform=uniform)
            obs_sum = net_observed(rnd, netstate[1], d_idx, delivered)
            w_rows = jax.lax.dynamic_slice_in_dim(
                embed_w_stal(w_stal), shard_index() * n_local, n_local, 0)
            if codec is None:
                params = net_mix(w_rows, hist_full)
            else:
                mixed = net_mix(w_rows, hist_full)
                params = apply_consensus_correction(mixed, params,
                                                    decoded, gamma=gam)
            return (params, opt_state, gstate, sim, netstate, resid,
                    hat), (edges, delivered, stale_counts, obs_sum)

        def round_body_sharded(carry, xs):
            # Per-device body under shard_map: params/opt_state/batch are
            # the device's [n_local, ...] shard; gstate/sim/edges stay
            # replicated at logical n.  Under the codec the gather
            # collective moves the wire arrays instead of the dense
            # params — the node-axis traffic is the compressed payload.
            if net is not None:
                return round_body_sharded_net(carry, xs)
            params, opt_state, gstate, sim, netstate, resid, hat = carry
            rnd, batch = xs
            params, opt_state = local_step(params, opt_state, batch)
            full = decoded_full = None
            if codec is not None:
                if collective == "gather":
                    # hat is carried replicated at full n_pad: encode
                    # the own rows' delta against its matching slice,
                    # gather the wire, and rebuild the whole decoded
                    # population as hat + decode(gathered deltas) —
                    # which is the next round's hat.  Row-wise codec
                    # ops, so the gathered decode is bitwise the
                    # senders' local decode of the same rows.
                    off = shard_index() * n_local
                    wire, decoded, resid = comp(
                        params, slice_rows(hat, off), resid)
                    decoded_full = jax.tree_util.tree_map(
                        jnp.add, hat, decode_rows(gather_full(wire),
                                                  params))
                    hat = decoded_full
                else:
                    # psum mode only ever needs the local rows' replica.
                    wire, decoded, resid = comp(params, hat, resid)
                    hat = decoded
            elif collective == "gather":
                full = gather_full(params)
            if sim_fn is not None and collective == "gather":
                src = decoded_full if codec is not None else full
                logical = jax.tree_util.tree_map(lambda x: x[:n], src)
                sim = refresh_sim(rnd, logical, sim)
            elif sim_fn is not None:
                # psum mode has no standing gather; pull the population in
                # only on refresh rounds (the cond predicate is replicated,
                # so every device takes the same branch and the collective
                # stays well-formed).
                def psum_mode_refresh(p, s):
                    if codec is not None:
                        # The replicas are dense f32, so this refresh
                        # gather costs dense bytes — a sim_every-gated
                        # control-plane cost, not the per-round data
                        # plane (DESIGN.md §13).
                        logical = jax.tree_util.tree_map(
                            lambda x: jax.lax.all_gather(
                                x, axes, axis=0, tiled=True)[:n],
                            decoded)
                    else:
                        logical = jax.tree_util.tree_map(
                            lambda x: jax.lax.all_gather(
                                x, axes, axis=0, tiled=True)[:n], p)
                    return sim_fn(logical).astype(jnp.float32)
                sim = jax.lax.cond(rnd % cfg.sim_every == 0,
                                   psum_mode_refresh,
                                   lambda p, s: s, params, sim)
            gstate, edges, w = strategy.graph_round(gstate, rnd, sim)
            w_pad = embed_w(w.astype(jnp.float32))
            if collective == "gather":
                w_rows = jax.lax.dynamic_slice_in_dim(
                    w_pad, shard_index() * n_local, n_local, 0)
                if codec is None:
                    params = mix_rows(w_rows, full)
                else:
                    mixed = mix_rows(w_rows, decoded_full)
                    params = apply_consensus_correction(mixed, params,
                                                        decoded, gamma=gam)
            else:
                w_cols = jax.lax.dynamic_slice_in_dim(
                    w_pad, shard_index() * n_local, n_local, 1)
                if codec is None:
                    params = mix_psum(w_cols, params)
                else:
                    # Contributions (including the self partial) come
                    # from the decoded payload; the consensus correction
                    # restores the exact local model after the reduce.
                    # The collective itself still moves f32 partials —
                    # compression shrinks the psum schedule's memory, not
                    # its collective bytes (documented in DESIGN.md §13).
                    mixed = mix_psum(w_cols, decoded)
                    params = apply_consensus_correction(mixed, params,
                                                        decoded, gamma=gam)
            return (params, opt_state, gstate, sim, netstate, resid,
                    hat), edges

        def round_body_sparse(carry, xs):
            # Sparse-native single-device body: the strategy returns CSR
            # adjacency directly and mixing is the O(n·k·D) gather
            # contraction — no [n, n] matrix is ever materialized.
            params, opt_state, gstate, sim, netstate, resid, hat = carry
            rnd, batch = xs
            params, opt_state = local_step(params, opt_state, batch)
            if codec is not None:
                wire, decoded, resid = comp(params, hat, resid)
                hat = decoded
                ctrl_src = decoded if codec.sim else params
            else:
                ctrl_src = params
            gstate, adj = strategy.graph_round(
                gstate, rnd, ctrl_src if needs_params else None)
            if codec is None:
                params = _sparse_mix(adj, params)
            else:
                params = apply_consensus_correction(
                    _sparse_mix(adj, decoded), params, decoded, gamma=gam)
            return (params, opt_state, gstate, sim, netstate, resid,
                    hat), (adj.idx, adj.mask)

        def sparse_mix_psum(apad, local, off):
            # Push / reduce-scatter schedule: each device accumulates its
            # local *senders'* contributions to every receiver
            # ([n_pad, D] partial), psum_scatters that partial down to
            # its own receiver block, then adds the self term locally —
            # collective result bytes are n_pad·D / num_devices per leaf
            # and compute stays O(n·k·D).  Compressed runs pass the
            # decoded payload as ``local``; the consensus correction
            # outside restores the exact local model.
            local_w = jnp.where(
                apad.mask & (apad.idx >= off) & (apad.idx < off + n_local),
                apad.w, 0.0)
            lidx = jnp.clip(apad.idx - off, 0, n_local - 1)
            ws_own = jax.lax.dynamic_slice_in_dim(apad.w_self, off,
                                                  n_local, 0)
            def one(leaf):
                flat = leaf.reshape(n_local, -1).astype(jnp.float32)
                d = flat.shape[1]
                cd = d if mix_chunk_d is None else min(mix_chunk_d, d)
                # feature-chunked partials bound the [n_pad, k, chunk]
                # gather buffer; a single psum_scatter over the
                # concatenated partial keeps the collective schedule
                # (and its bitwise result) identical to the unchunked
                # contraction.
                part = jnp.concatenate(
                    [jnp.einsum("nk,nkd->nd", local_w,
                                flat[:, s:s + cd][lidx],
                                precision=jax.lax.Precision.HIGHEST)
                     for s in range(0, d, cd)], axis=1) \
                    if cd < d else \
                    jnp.einsum("nk,nkd->nd", local_w, flat[lidx],
                               precision=jax.lax.Precision.HIGHEST)
                own = jax.lax.psum_scatter(part, axes,
                                           scatter_dimension=0, tiled=True)
                own = own + ws_own[:, None] * flat
                return own.reshape(leaf.shape).astype(leaf.dtype)
            return jax.tree_util.tree_map(one, local)

        def round_body_sharded_sparse(carry, xs):
            # Per-device sparse body: gstate and the CSR round output stay
            # replicated at logical n; only the params move, and only to
            # the extent the schedule needs them.  Under the codec the
            # standing gather moves the wire arrays (encoded deltas);
            # receivers rebuild the decoded population from the
            # replicated hat.
            params, opt_state, gstate, sim, netstate, resid, hat = carry
            rnd, batch = xs
            params, opt_state = local_step(params, opt_state, batch)
            off = shard_index() * n_local
            if codec is not None:
                hat_own = slice_rows(hat, off) \
                    if collective == "gather" else hat
                wire, decoded, resid = comp(params, hat_own, resid)
            full = full_dec = None
            if collective == "gather":
                if codec is None:
                    full = gather_full(params)
                else:
                    full_dec = jax.tree_util.tree_map(
                        jnp.add, hat, decode_rows(gather_full(wire),
                                                  params))
                    hat = full_dec
            elif codec is not None:
                hat = decoded
            if not needs_params:
                ctrl = None
            elif collective == "gather":
                src = full_dec if codec is not None else full
                ctrl = jax.tree_util.tree_map(lambda x: x[:n], src)
            else:
                # psum mode has no standing gather; pull the population
                # in only on negotiation rounds (the replicated predicate
                # keeps the collective well-formed, exactly like
                # psum_mode_refresh above).  Under the codec the dense
                # f32 replicas are gathered — a ctrl_every-gated
                # control-plane cost (DESIGN.md §13).
                def ctrl_gather(p):
                    if codec is not None:
                        return jax.tree_util.tree_map(
                            lambda x: jax.lax.all_gather(
                                x, axes, axis=0, tiled=True)[:n],
                            decoded)
                    return jax.tree_util.tree_map(
                        lambda x: jax.lax.all_gather(
                            x, axes, axis=0, tiled=True)[:n], p)
                def ctrl_hold(p):
                    return jax.tree_util.tree_map(
                        lambda x: jnp.zeros((n,) + x.shape[1:],
                                            jnp.float32 if codec is not None
                                            else x.dtype),
                        p)
                ctrl = jax.lax.cond(rnd % ctrl_every == 0, ctrl_gather,
                                    ctrl_hold, params)
            gstate, adj = strategy.graph_round(gstate, rnd, ctrl)
            apad = pad_adjacency(adj, n_pad)
            if collective == "gather":
                sl = lambda a: jax.lax.dynamic_slice_in_dim(
                    a, off, n_local, 0)
                adj_l = SparseAdjacency(sl(apad.idx), sl(apad.w),
                                        sl(apad.w_self), sl(apad.mask))
                rows = off + jnp.arange(n_local, dtype=jnp.int32)
                if codec is None:
                    params = _sparse_mix(adj_l, full, rows=rows)
                else:
                    params = apply_consensus_correction(
                        _sparse_mix(adj_l, full_dec, rows=rows),
                        params, decoded, gamma=gam)
            elif codec is None:
                params = sparse_mix_psum(apad, params, off)
            else:
                params = apply_consensus_correction(
                    sparse_mix_psum(apad, decoded, off), params, decoded,
                    gamma=gam)
            return (params, opt_state, gstate, sim, netstate, resid,
                    hat), (adj.idx, adj.mask)

        if sparse_native:
            body = round_body_sharded_sparse if sharded \
                else round_body_sparse
        else:
            body = round_body_sharded if sharded else round_body

        if stream is None:
            def superstep(carry, rnds, batches):
                return jax.lax.scan(body, carry, (rnds, batches))
        else:
            def superstep(carry, rnds, data, index, sizes, ids):
                def step(c, rnd):
                    batch = stream.draw(data, index, sizes, ids, rnd)
                    return body(c, (rnd, batch))
                return jax.lax.scan(step, carry, rnds)

        if sharded:
            net_specs = ()
            if net is not None:
                net_specs = (
                    jax.tree_util.tree_map(self._leaf_pspec,
                                           self._netstate[0]),
                    P())                       # lhist stays replicated
            carry_specs = (
                jax.tree_util.tree_map(self._leaf_pspec, self._params),
                jax.tree_util.tree_map(self._leaf_pspec, self._opt_state),
                jax.tree_util.tree_map(lambda _: P(), self.gstate),
                P(),
                net_specs,
                jax.tree_util.tree_map(self._leaf_pspec, self._resid),
                # gather mode carries the full replicated hat; psum mode
                # shards it with the params (see the hat init above).
                jax.tree_util.tree_map(
                    (lambda _: P()) if collective == "gather"
                    else self._leaf_pspec, self._hat))
            if sparse_native:
                self._ys_specs = (P(), P())   # (idx, mask), replicated
            else:
                self._ys_specs = P() if net is None \
                    else (P(), P(), P(), P())
            if stream is None:
                # batch stacks are [K, n_pad, b, ...]: node axis = dim 1.
                self._batch_spec = P(None, self._nspec)
                xs_specs = (P(), None)        # batch tree filled per chunk
            else:
                # (rnds, data, index, sizes, ids): the shared dataset is
                # replicated; only the per-node tables are node-sharded.
                xs_specs = (P(), P(), P(self._nspec), P(self._nspec),
                            P(self._nspec))
            self._carry_specs = carry_specs
            self._xs_specs = xs_specs
            self._superstep_fn = superstep
            self._superstep = None            # built lazily (needs the
                                              # batch pytree for in_specs)
        else:
            self._superstep = jax.jit(superstep)

        if stream is not None:
            if sharded:
                put_r = lambda x: jax.device_put(
                    jnp.asarray(x), NamedSharding(mesh, P()))
                put_s = lambda x: jax.device_put(
                    jnp.asarray(x), NamedSharding(mesh, P(self._nspec)))
            else:
                put_r = put_s = jnp.asarray
            self._stream_args = (
                jax.tree_util.tree_map(put_r, stream.data),
                put_s(_pad_nodes(stream.index, self.n_pad)),
                put_s(_pad_nodes(stream.sizes, self.n_pad)),
                put_s(jnp.arange(self.n_pad, dtype=jnp.int32)))

        self._evaluate = jax.jit(
            make_evaluator(eval_fn, batch_chunk=eval_batch_chunk))

    # ------------------------------------------------------------------

    def _leaf_pspec(self, leaf) -> P:
        """PartitionSpec for one state leaf: node-sharded on dim 0 when it
        carries the padded node axis, replicated otherwise (scalar
        optimizer counters and the like)."""
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == self.n_pad:
            return P(self._nspec)
        return P()

    @property
    def params(self):
        """Node-stacked parameters, logical ``[n, ...]`` view (padded
        rows are internal to sharded mode)."""
        if self.n_pad == self.cfg.n_nodes:
            return self._params
        return jax.tree_util.tree_map(
            lambda x: x[:self.cfg.n_nodes], self._params)

    @property
    def opt_state(self):
        """Optimizer state, logical ``[n, ...]`` view."""
        if self.n_pad == self.cfg.n_nodes:
            return self._opt_state
        return jax.tree_util.tree_map(
            lambda x: x[:self.cfg.n_nodes] if getattr(x, "ndim", 0) >= 1
            and x.shape[0] == self.n_pad else x, self._opt_state)

    def _get_superstep(self, batches) -> Callable:
        """The jitted superstep; in sharded mode, wrap in shard_map on
        first use (prefetch mode needs the batch pytree structure for its
        in_specs)."""
        if self._superstep is not None:
            return self._superstep
        if self.stream is None:
            batch_specs = jax.tree_util.tree_map(
                lambda _: self._batch_spec, batches)
            in_specs = (self._carry_specs, P(), batch_specs)
        else:
            data_specs = jax.tree_util.tree_map(
                lambda _: self._xs_specs[1], self._stream_args[0])
            in_specs = (self._carry_specs, self._xs_specs[0], data_specs,
                        self._xs_specs[2], self._xs_specs[3],
                        self._xs_specs[4])
        self._superstep = jax.jit(jax.shard_map(
            self._superstep_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(self._carry_specs, self._ys_specs),
            check_vma=False))
        return self._superstep

    def _prefetch_batches(self, k: int):
        """Draw ``k`` rounds' worth of host batches and stack them into
        the ``[K, n_pad, b, ...]`` pytree the superstep consumes
        (advances the host batcher by ``k`` draws)."""
        host_batches = [self.batcher.next() for _ in range(k)]
        batches = {key: jnp.asarray(
            np.stack([b[key] for b in host_batches]))
            for key in host_batches[0]}
        if self.n_pad != self.cfg.n_nodes:
            batches = {key: jnp.pad(
                v, [(0, 0), (0, self.n_pad - self.cfg.n_nodes)]
                + [(0, 0)] * (v.ndim - 2), mode="edge")
                for key, v in batches.items()}
        return batches

    def compiled_hlo(self, chunk: Optional[int] = None,
                     start: int = 0) -> str:
        """Compile — without executing — one ``chunk``-round superstep
        and return its post-optimization HLO text.

        This is the autotuner's stage-1 surface: candidates are lowered
        and costed with :func:`repro.launch.hlo_cost.analyse_hlo` (the
        trip-count-aware model, so the scan body is weighted by
        ``chunk``) before a single round is ever run.  In host-batcher
        mode this draws ``chunk`` batches to obtain the input pytree
        (the batcher advances; use a fresh engine if that matters).
        """
        k = chunk or self.chunk or self.cfg.eval_every
        rnds = jnp.arange(start, start + k)
        carry = (self._params, self._opt_state, self.gstate, self.sim,
                 self._netstate, self._resid, self._hat)
        if self.stream is None:
            batches = self._prefetch_batches(k)
            lowered = self._get_superstep(batches).lower(
                carry, rnds, batches)
        else:
            lowered = self._get_superstep(None).lower(
                carry, rnds, *self._stream_args)
        return lowered.compile().as_text()

    def _run_chunk(self, start: int, end: int) -> np.ndarray:
        """Execute rounds ``[start, end]`` as one on-device superstep and
        decode the stacked per-round edge matrices (``[K, n, n]`` bool,
        logical n)."""
        k = end - start + 1
        rnds = jnp.arange(start, end + 1)
        carry = (self._params, self._opt_state, self.gstate, self.sim,
                 self._netstate, self._resid, self._hat)
        if self.stream is None:
            batches = self._prefetch_batches(k)
            fn = self._get_superstep(batches)
            carry, ys = fn(carry, rnds, batches)
        else:
            fn = self._get_superstep(None)
            carry, ys = fn(carry, rnds, *self._stream_args)
        (self._params, self._opt_state, self.gstate, self.sim,
         self._netstate, self._resid, self._hat) = carry
        if hasattr(self.strategy, "set_graph_state"):
            self.strategy.set_graph_state(self.gstate, self.sim)
        if self.sparse_native:
            # CSR scan output: [K, n, k] sender indices + validity mask.
            idx_np = np.asarray(ys[0], np.int32)
            mask_np = np.asarray(ys[1], bool)
            self._comm_bytes += int(mask_np.sum()) * self._wire_bytes
            self._last_isolated = int((~mask_np[-1].any(axis=1)).sum())
            nn = self.cfg.n_nodes
            if nn > SPARSE_EDGE_DECODE_MAX:
                # Paper-scale n: never materialize [n, n] on the host —
                # edge_history carries the compact (idx, mask) pairs.
                self.edge_history.extend(
                    (idx_np[t], mask_np[t]) for t in range(len(idx_np)))
                return mask_np
            dense = np.zeros((idx_np.shape[0], nn, nn), bool)
            t_i, r_i, s_i = np.nonzero(mask_np)
            dense[t_i, r_i, idx_np[t_i, r_i, s_i]] = True
            self.edge_history.extend(dense)
            return dense
        if self.net is None:
            edges_np = np.asarray(ys, bool)
            self.edge_history.extend(edges_np)
            self._comm_bytes += int(edges_np.sum()) * self._wire_bytes
            return edges_np
        # net mode: decode (negotiated, delivered, staleness) stacks —
        # comm bytes count the transfers that actually arrived, exactly
        # like the event-driven runner's per-arrival accounting.
        edges_stack, delivered_stack, stale_stack, obs_stack = ys
        edges_np = np.asarray(edges_stack, bool)
        delivered_np = np.asarray(delivered_stack, bool)
        self.edge_history.extend(edges_np)
        self.delivered_history.extend(delivered_np)
        n_del = int(delivered_np.sum())
        self._comm_bytes += n_del * self._wire_bytes
        self.net_stats["delivered"] += n_del
        self.net_stats["dropped"] += int(edges_np.sum()) - n_del
        self.net_stats["staleness_hist"] += \
            np.asarray(stale_stack, np.int64).sum(axis=0)
        self.net_stats["staleness_sum"] += int(
            np.asarray(obs_stack, np.int64).sum())
        return edges_np

    def staleness_mean(self) -> float:
        """Mean delivered content-staleness in rounds (0.0 when nothing
        was delivered or no network model is attached) — the dense
        counterpart of ``NetMetricsLog.staleness_mean``."""
        return net_staleness_mean(self.net_stats)

    def evaluate(self, rnd: int, edges: np.ndarray) -> RoundRecord:
        """Evaluate every node on the shared test set after round ``rnd``
        and append the §IV-A4 :class:`RoundRecord` (mean accuracy/loss,
        inter-node variance, cumulative comm bytes, isolation count)."""
        losses, metrics = self._evaluate(self.params, self.test_batch)
        rec = make_round_record(rnd, losses, metrics, self._comm_bytes,
                                edges, isolated=self._last_isolated)
        self.log.add(rec)
        return rec

    def run(self, progress: Optional[Callable[[RoundRecord], None]] = None
            ) -> MetricsLog:
        """Run all ``cfg.rounds`` rounds in eval-boundary-aligned
        supersteps; returns the same :class:`MetricsLog` the host runner
        would produce for this trajectory.  A ``chunk`` cap subdivides
        long eval chunks into fixed-size supersteps (same trajectory and
        log bit for bit — the scan body is identical, only the number of
        rounds per dispatch changes)."""
        for start, end in eval_boundaries(self.cfg.rounds,
                                          self.cfg.eval_every):
            s = start
            while True:
                e = end if not self.chunk \
                    else min(s + self.chunk - 1, end)
                edges_np = self._run_chunk(s, e)
                if e == end:
                    break
                s = e + 1
            rec = self.evaluate(end, edges_np[-1])
            if progress is not None:
                progress(rec)
        return self.log

    def run_steps(self, rounds: int, chunk: Optional[int] = None) -> None:
        """Throughput mode: run ``rounds`` rounds in fixed-size supersteps
        with no evaluation — the fig9/fig10 benchmark loop and the
        autotuner's stage-2 micro-run.  ``chunk`` defaults to the
        engine's resolved chunk knob (all rounds in one superstep when
        neither is set)."""
        chunk = chunk or self.chunk or rounds
        start = 0
        while start < rounds:
            end = min(start + chunk, rounds) - 1
            self._run_chunk(start, end)
            start = end + 1
