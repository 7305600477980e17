"""Decentralized-learning round loop (the paper's experiment engine).

Runs Algorithm 1/2 semantics for a population of n nodes whose parameters
are stacked on a leading node axis:

  per round:  local SGD step per node (vmapped)
              -> strategy emits (edges, W)        [host control plane]
              -> params <- W @ params             [device mixing]

The strategy is any :class:`repro.core.TopologyStrategy` — Static,
Fully-Connected, Epidemic Learning, or the full Morph protocol — so the
paper's Table I / Figs. 3-7 are one loop with four strategies.  Evaluation
follows §IV-A4: every node on the shared test set, mean + inter-node
variance.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import apply_mixing, isolated_nodes
from ..data.pipeline import StackedBatcher
from ..optim import Optimizer, apply_updates
from .metrics import MetricsLog, RoundRecord, internode_variance


@dataclass
class RunnerConfig:
    """Experiment knobs shared by every runtime (units in comments).

    The first block is the paper's experiment grid; the second selects
    and tunes the compiled superstep engine (``dlrt.compiled``); the
    third shards that engine over a device mesh (DESIGN.md §8).
    """
    n_nodes: int                           # population size n
    rounds: int                            # total training rounds
    eval_every: int = 20                   # evaluation cadence (rounds)
    model_bytes: Optional[int] = None      # per-transfer payload (default:
                                           # actual param bytes)
    sim_every: int = 1                     # recompute stacked sims every r
    seed: int = 0
    # Compiled-superstep dispatch (dlrt.compiled): None = auto (use the
    # fused lax.scan engine whenever the strategy is in-graph-capable),
    # True = require it, False = force the per-round host loop.
    compiled: Optional[bool] = None
    use_pallas: bool = False               # Pallas sim + fused mixing
    interpret: bool = False                # Pallas interpret mode (CPU)
    # Performance knobs of the compiled engine.  Each accepts the
    # literal string "auto": the runner then resolves it through the
    # repro.tune cache for this run's (backend, n, D, devices, net)
    # shape — falling back to the hand-set default below when no cache
    # entry exists — before the engine is built, so an "auto" run is
    # bit-identical to passing the resolved values explicitly.
    block_d: Optional[object] = None       # kernel D-block (int | "auto")
    # Superstep length cap in rounds per compiled dispatch (int |
    # "auto"); None fuses each whole eval chunk.  Trajectory-invariant.
    chunk: Optional[object] = None
    # Sharded superstep (compiled engine only): shard the node axis over
    # this many devices via shard_map.  None = single-device engine;
    # 0 = every local device; N > 0 = exactly N devices (error if the
    # host has fewer — simulate with XLA_FLAGS=--xla_force_host_platform_
    # device_count=N on CPU).
    mesh_devices: Optional[int] = None
    # Sharded mixing schedule: "gather" (row-block of W applied to the
    # all-gathered population; bitwise-matches the single-device engine),
    # "psum" (partial-products reduce; f32-rounding-close), or "auto".
    collective: str = "gather"
    # Engine data/control plane: "dense" (the original O(n²) path),
    # "sparse" (CSR k-sparse mixing + gossiped discovery, DESIGN.md
    # §11), or "auto" (resolved through the repro.tune cache like the
    # other knobs).  Sparse-native strategies
    # (repro.sparse.SparseMorphStrategy / SparseEpidemicStrategy)
    # require "sparse" (or "auto", which then resolves to it).
    engine: str = "dense"
    # Compat-mode numerics when a dense-returning strategy runs under
    # engine="sparse": "exact" (identical dense contraction — bitwise vs
    # the dense engine) or "gather" (in-scan CSR conversion + sparse
    # gather mix — parity to tolerance).
    sparse_mix: str = "exact"
    # Chunked per-layer exchange (DESIGN.md §12): cap on flattened
    # feature elements per mixing-contraction step, bounding the
    # engine's f32-upcast / neighbor-gather buffers at
    # O(n · mix_chunk_d) — required headroom for multi-MB CNN params.
    # The node/slot contraction axis is never split: dense mixing is
    # invariant to this knob up to last-ulp dot-kernel rounding
    # (bitwise at small shapes), the sparse gather path last-ulp
    # allclose (identical edges).  None = whole-leaf contractions.
    mix_chunk_d: Optional[int] = None
    # Evaluate the shared test set at most this many samples per vmapped
    # forward pass (chunk means recombined by sample-count weights) —
    # bounds the [n, b_test, ...] activation footprint at eval
    # boundaries.  f32-rounding-close across chunkings, not bitwise;
    # None = single whole-batch pass.
    eval_batch_chunk: Optional[int] = None
    # Dense in-scan network model (repro.netsim.DenseNetwork): price
    # latency/staleness/drops/churn inside the fused superstep
    # (DESIGN.md §9).  None = idealized lockstep network.  Requires the
    # compiled engine (an in-graph strategy) and, when sharded,
    # collective="gather".
    net: Optional[object] = None
    # Compressed gossip (repro.compress, DESIGN.md §13): what every
    # model transfer carries on the wire.  "none" (default, bitwise-
    # identical to the pre-compression engines), a codec spec string —
    # "int8" | "fp8" | "topk[frac]" | combinations like "int8+topk0.25"
    # — a repro.compress.CompressConfig, or "auto" (resolved through
    # the repro.tune cache like the other knobs).  Error-feedback
    # residuals ride in the scan carry; comm-byte accounting and the
    # dense network model's serialization delay switch to the analytic
    # wire bytes.  Requires the compiled engine and the XLA mixing
    # paths (use_pallas=False).
    compress: object = "none"


def make_local_step(loss_fn: Callable, optimizer: Optimizer) -> Callable:
    """Vmapped per-node SGD step — the same traced function whether it
    runs per round (host loop) or inside the superstep scan."""
    def local_step(params, opt_state, batch):
        def one(p, s, b):
            grads = jax.grad(lambda q: loss_fn(q, b)[0])(p)
            upd, s = optimizer.update(grads, s, p)
            return apply_updates(p, upd), s
        return jax.vmap(one)(params, opt_state, batch)
    return local_step


def make_evaluator(eval_fn: Callable,
                   batch_chunk: Optional[int] = None) -> Callable:
    """Vmapped every-node evaluation on the shared test batch: returns
    ``(losses [n], metrics dict of [n] arrays)``.

    ``batch_chunk`` caps how many test samples each vmapped forward pass
    sees: the test batch is split on its leading axis and the per-chunk
    mean losses/metrics are recombined by sample-count weights — the
    memory-aware eval boundary for image models, where the whole-batch
    ``[n, b_test, H, W, C]`` activation stack is the peak allocation.
    Assumes ``eval_fn`` returns *mean* statistics over its batch (both
    in-repo eval fns do).  The recombination introduces one extra f32
    rounding per chunk, so results are allclose — not bitwise — across
    different chunkings.
    """
    def evaluate(params, test):
        per_node = lambda t: jax.vmap(lambda p: eval_fn(p, t))(params)
        if batch_chunk is None:
            return per_node(test)
        b = jax.tree_util.tree_leaves(test)[0].shape[0]
        if b <= batch_chunk:
            return per_node(test)
        whole = b // batch_chunk
        # lax.map runs the chunks one after another.  Unrolled, they are
        # independent, and the TPU scheduler overlapped them: every
        # chunk's activations were live at once, which is the peak this
        # knob exists to bound.
        mapped = jax.lax.map(per_node, jax.tree_util.tree_map(
            lambda x: x[:whole * batch_chunk].reshape(
                (whole, batch_chunk) + x.shape[1:]), test))
        pieces = [(batch_chunk, jax.tree_util.tree_map(lambda x: x[i],
                                                        mapped))
                  for i in range(whole)]
        if whole * batch_chunk < b:
            pieces.append((b - whole * batch_chunk, per_node(
                jax.tree_util.tree_map(lambda x: x[whole * batch_chunk:],
                                       test))))
        losses, metrics = None, None
        for size, (pl, pm) in pieces:
            wl = pl * (size / b)
            wm = {k: v * (size / b) for k, v in pm.items()}
            losses = wl if losses is None else losses + wl
            metrics = wm if metrics is None \
                else {k: metrics[k] + wm[k] for k in metrics}
        return losses, metrics
    return evaluate


def stacked_model_bytes(params, n_nodes: int) -> int:
    """Per-transfer payload: one node's slice of the stacked params."""
    return sum(x.nbytes // n_nodes
               for x in jax.tree_util.tree_leaves(params))


def net_staleness_mean(net_stats) -> float:
    """Mean delivered content-staleness in rounds from a dense-network
    ``net_stats`` dict (0.0 when absent or nothing was delivered) — the
    one formula behind both the runner's and the engine's
    ``staleness_mean`` methods."""
    if not net_stats or not net_stats["delivered"]:
        return 0.0
    return net_stats["staleness_sum"] / net_stats["delivered"]


def make_round_record(rnd: int, losses, metrics, comm_bytes: int,
                      edges: np.ndarray,
                      isolated: Optional[int] = None) -> RoundRecord:
    """§IV-A4 metrics for one evaluation point — the single constructor
    both the host loop and the compiled engine decode into, so their
    logs cannot drift apart field by field.

    ``isolated`` overrides the dense-edge count: the sparse engine
    already knows the in-degree-0 rows from the CSR mask and, at
    paper-scale n, never materializes an ``[n, n]`` matrix to count
    from.  ``None`` (every dense path) counts from ``edges``.
    """
    acc = np.asarray(metrics["accuracy"])
    return RoundRecord(
        rnd=rnd,
        mean_accuracy=float(acc.mean()),
        mean_loss=float(np.asarray(losses).mean()),
        internode_variance=internode_variance(acc),
        comm_bytes=comm_bytes,
        isolated=isolated if isolated is not None
        else len(isolated_nodes(edges)),
        per_node_accuracy=acc,
    )


class DecentralizedRunner:
    """Strategy-agnostic D-PSGD runner over stacked node params."""

    def __init__(self, *, init_fn: Callable, loss_fn: Callable,
                 eval_fn: Callable, optimizer: Optimizer,
                 batcher: StackedBatcher, test_batch: Dict[str, np.ndarray],
                 strategy, cfg: RunnerConfig):
        self.cfg = cfg
        self.strategy = strategy
        self.batcher = batcher
        self.test_batch = {k: jnp.asarray(v) for k, v in test_batch.items()}
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed), cfg.n_nodes)
        self.params = jax.vmap(init_fn)(keys)
        self.opt = optimizer
        self.opt_state = jax.vmap(optimizer.init)(self.params)
        self._loss_fn = loss_fn
        self._eval_fn = eval_fn
        self.log = MetricsLog()
        self.edge_history: list = []       # per-round in-edge matrices
        self.delivered_history: list = []  # per-round delivered edges
                                           # (cfg.net runs only)
        self.net_stats = None              # dense-network counters ditto
        self.resolved_knobs = None         # set when the compiled engine
                                           # is built (repro.tune)
        self.engine = None                 # the last compiled run's engine
        self._comm_bytes = 0
        self._model_bytes = cfg.model_bytes \
            or stacked_model_bytes(self.params, cfg.n_nodes)

        @jax.jit
        def mix(params, w):
            return apply_mixing(w, params, chunk_d=cfg.mix_chunk_d)

        self._local_step = jax.jit(make_local_step(loss_fn, optimizer))
        self._mix = mix
        self._evaluate = jax.jit(
            make_evaluator(eval_fn, batch_chunk=cfg.eval_batch_chunk))

    # ------------------------------------------------------------------

    def _round(self, rnd: int) -> np.ndarray:
        batch = {k: jnp.asarray(v) for k, v in self.batcher.next().items()}
        self.params, self.opt_state = self._local_step(
            self.params, self.opt_state, batch)
        stacked = jax.device_get(self.params) \
            if rnd % self.cfg.sim_every == 0 else None
        edges, w = self.strategy.round_edges(rnd, stacked)
        self.edge_history.append(np.array(edges, dtype=bool))
        self.params = self._mix(self.params, jnp.asarray(w, jnp.float32))
        self._comm_bytes += int(edges.sum()) * self._model_bytes
        return edges

    def staleness_mean(self) -> float:
        """Mean delivered content-staleness in rounds from the last
        compiled run's dense-network counters (0.0 when no network model
        ran or nothing was delivered)."""
        return net_staleness_mean(self.net_stats)

    def evaluate(self, rnd: int, edges: np.ndarray) -> RoundRecord:
        """Evaluate every node on the shared test set after round ``rnd``
        and append the §IV-A4 :class:`RoundRecord`."""
        losses, metrics = self._evaluate(self.params, self.test_batch)
        rec = make_round_record(rnd, losses, metrics, self._comm_bytes,
                                edges)
        self.log.add(rec)
        return rec

    def _make_engine(self):
        """Build the fused lax.scan engine sharing this runner's live
        params/optimizer state (dlrt.compiled; imported lazily — it
        imports RunnerConfig from here).

        ``cfg.mesh_devices`` promotes the engine to sharded mode: the
        node axis is sharded over a 1-D device mesh and the scan body's
        cross-node ops run as collectives (DESIGN.md §8).  A
        :class:`repro.data.DeviceDataStream` passed as ``batcher`` is
        detected here and routed to the engine's in-scan batch drawing.

        ``"auto"`` knobs (``cfg.block_d`` / ``cfg.collective`` /
        ``cfg.chunk``) are resolved here against the ``repro.tune``
        cache; the concrete values land in ``self.resolved_knobs``
        (DESIGN.md §10).
        """
        from ..compress import CompressConfig
        from ..launch.mesh import make_superstep_mesh
        from ..tune import AUTO, resolve_knobs
        from .compiled import CompiledSuperstep
        knobs = resolve_knobs(self.cfg, self.params)
        self.resolved_knobs = knobs
        codec = CompressConfig.parse(knobs.compress)
        engine = knobs.engine
        if self.cfg.engine == AUTO and getattr(self.strategy, "sparse",
                                               False):
            # A sparse-native strategy determines the data plane; an
            # "auto" resolution (or a stale dense cache entry) must not
            # steer it onto the dense path.  An explicit engine="dense"
            # still raises the documented TypeError in the engine.
            engine = "sparse"
        mesh = None
        if self.cfg.mesh_devices is not None:
            mesh = make_superstep_mesh(self.cfg.mesh_devices or None)
        stream = self.batcher if hasattr(self.batcher, "draw") else None
        return CompiledSuperstep(
            init_fn=None, loss_fn=self._loss_fn, eval_fn=self._eval_fn,
            optimizer=self.opt,
            batcher=None if stream is not None else self.batcher,
            data_stream=stream,
            test_batch=self.test_batch, strategy=self.strategy,
            cfg=self.cfg, use_pallas=self.cfg.use_pallas,
            interpret=self.cfg.interpret, block_d=knobs.block_d,
            mesh=mesh, collective=knobs.collective, net=self.cfg.net,
            chunk=knobs.chunk, engine=engine,
            sparse_mix=self.cfg.sparse_mix,
            mix_chunk_d=self.cfg.mix_chunk_d,
            eval_batch_chunk=self.cfg.eval_batch_chunk,
            compress=codec,
            params=self.params, opt_state=self.opt_state)

    def run(self, progress: Optional[Callable[[RoundRecord], None]] = None
            ) -> MetricsLog:
        """Run all ``cfg.rounds`` rounds and return the metrics log.

        Dispatch: ``cfg.compiled=None`` auto-selects the fused superstep
        engine for in-graph-capable strategies (sharded when
        ``cfg.mesh_devices`` is set) and the per-round host loop
        otherwise; True/False force one path.  ``progress`` is invoked
        with each evaluation's :class:`RoundRecord`.  A compiled run
        leaves its :class:`repro.dlrt.compiled.CompiledSuperstep` in
        ``self.engine``, for throughput runs and HLO inspection."""
        use_compiled = self.cfg.compiled
        if use_compiled is None:
            use_compiled = getattr(self.strategy, "in_graph", False)
        if use_compiled:
            engine = self._make_engine()
            self.engine = engine
            log = engine.run(progress)
            self.params, self.opt_state = engine.params, engine.opt_state
            self.edge_history = engine.edge_history
            self.delivered_history = engine.delivered_history
            self.net_stats = engine.net_stats
            self._comm_bytes = engine._comm_bytes
            self.log = log
            return log
        if getattr(self.strategy, "sparse", False):
            raise TypeError(
                "sparse-native strategies (CSR graph_round) only run "
                "inside the compiled superstep engine — leave "
                "cfg.compiled unset (auto) or set it True")
        if self.cfg.net is not None:
            raise TypeError(
                "RunnerConfig.net (the dense in-scan network model) "
                "requires the compiled superstep engine — use an "
                "in-graph strategy, or the event-driven "
                "repro.netsim.AsyncRunner for host-path network runs")
        comp = self.cfg.compress
        if comp is not None and comp != "none":
            from ..compress import CompressConfig
            if comp == "auto" or not isinstance(comp, CompressConfig) \
                    or comp.enabled:
                raise TypeError(
                    "RunnerConfig.compress (compressed gossip) carries "
                    "its error-feedback residual in the scan state and "
                    "requires the compiled superstep engine — use an "
                    "in-graph strategy, or compress='none' for the "
                    "per-round host loop")
        if hasattr(self.batcher, "draw"):
            raise TypeError(
                "DeviceDataStream draws batches inside the compiled scan; "
                "the per-round host loop needs a host batcher "
                "(StackedBatcher)")
        edges = np.zeros((self.cfg.n_nodes, self.cfg.n_nodes), bool)
        for rnd in range(self.cfg.rounds):
            edges = self._round(rnd)
            if rnd % self.cfg.eval_every == 0 \
                    or rnd == self.cfg.rounds - 1:
                rec = self.evaluate(rnd, edges)
                if progress is not None:
                    progress(rec)
        return self.log
