"""Sweep engine: whole *experiments* vmapped into one dispatch (DESIGN.md §14).

The compiled superstep (:mod:`repro.dlrt.compiled`) fuses K rounds of ONE
trajectory into a ``lax.scan``.  Sensitivity sweeps — seeds × network
profiles × Morph hyperparameters — still pay one dispatch (and one
python round-decode loop) per experiment.  This engine adds the missing
axis: a :class:`SweepSpec` declares E experiments and
:class:`SweepSuperstep` ``vmap``s the *entire round body* over them, so
hundreds of trajectories advance inside a single compiled scan.

Everything trajectory-defining is folded per-experiment:

* **parameters / optimizer state** — initialised per experiment from its
  own seed (exactly ``CompiledSuperstep``'s ``PRNGKey(cfg.seed)`` path)
  and stacked on a leading ``[E, ...]`` axis;
* **data** — one shared device-resident dataset, per-experiment
  ``[E, n, S]`` index tables (:func:`repro.data.stack_streams`), and the
  batch key built from a *traced* per-experiment seed
  (``DeviceDataStream.draw(..., seed=seed_e)``);
* **network model** — a :class:`repro.netsim.SweepNetwork` stacks one
  :class:`~repro.netsim.dense.DenseNetwork` per experiment; jitter/drop
  draws go through the always-draw folded twins in
  :mod:`repro.netsim.sampling`, fault timelines ride as ``[E, rounds,
  n]`` masks, and each experiment's staleness clamps to its own logical
  ring depth inside the shared physical ring;
* **hyperparameters** — ``delta_r`` / ``beta`` enter through the
  strategy's ``sweep_graph_round`` as traced scalars (cadence only feeds
  the ``lax.cond`` predicate, beta only scales the Gumbel-top-k logits).

**Conformance pin.**  For the dense gather path, a sweep of E
experiments is *bitwise identical* to E independent single-experiment
``CompiledSuperstep`` runs of the same configurations
(tests/test_sweep.py): every random draw is a pure function of
``(seed, round, node/edge)`` so folding the seed per-experiment changes
nothing, and under ``vmap`` each mixing contraction / SGD step runs the
same-shaped inner computation per experiment.  Two documented caveats:
``lax.cond`` on a *batched* predicate (a swept ``delta_r``) executes
both branches and selects — values are unchanged, cost is not — and
experiments with *different* ring depths share one physical ring, which
changes the staleness contraction's length (n·S_max vs n·S_e); equal-
depth sweeps (the pinned and benchmarked configurations) are exact.

**Sharding.**  ``mesh`` (:func:`repro.launch.mesh.make_sweep_mesh`)
splits the experiment axis over ``"exp"`` (embarrassingly parallel) and
optionally the node axis over ``"data"`` using the same gather-collective
schedule as the 1-D sharded superstep (no-net sweeps only).
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import apply_mixing
from ..core.mixing import tensordot_mix_leaf
from ..data.pipeline import DeviceDataStream, stack_streams
from ..netsim import sampling
from ..optim import Optimizer
from .compiled import (eval_boundaries, net_effective, net_observed,
                       net_push, net_select)
from .metrics import MetricsLog, RoundRecord
from .runtime import (RunnerConfig, make_evaluator, make_local_step,
                      make_round_record, net_staleness_mean,
                      stacked_model_bytes)


@dataclass(frozen=True)
class SweepSpec:
    """Declarative experiment axis: per-experiment tuples, zipped.

    ``seeds`` drives each experiment's parameter initialisation (the
    single engine's ``cfg.seed`` role).  ``profiles`` is an optional
    per-experiment *label* (typically the netsim profile name) carried
    into benchmark records; the actual network models arrive separately
    as a :class:`repro.netsim.SweepNetwork`.  ``delta_r`` / ``beta``
    are optional per-experiment Morph hyperparameters, routed through
    the strategy's ``sweep_graph_round`` as traced scalars.

    Build cross products with :meth:`grid`; all present axes must have
    length ``len(self)``.
    """

    seeds: Tuple[int, ...]
    profiles: Optional[Tuple[str, ...]] = None
    delta_r: Optional[Tuple[int, ...]] = None
    beta: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        if len(self.seeds) == 0:
            raise ValueError("SweepSpec needs at least one experiment")
        for name in ("profiles", "delta_r", "beta"):
            axis = getattr(self, name)
            if axis is not None and len(axis) != len(self.seeds):
                raise ValueError(
                    f"SweepSpec.{name} has {len(axis)} entries for "
                    f"{len(self.seeds)} experiments — per-experiment "
                    "axes are zipped, use SweepSpec.grid for cross "
                    "products")

    def __len__(self) -> int:
        return len(self.seeds)

    @classmethod
    def grid(cls, *, seeds: Sequence[int],
             profiles: Optional[Sequence[str]] = None,
             delta_r: Optional[Sequence[int]] = None,
             beta: Optional[Sequence[float]] = None) -> "SweepSpec":
        """Cross product of the provided axes: ``seeds`` varies fastest,
        then ``profiles``, ``delta_r``, ``beta`` — E = the product of
        the axis lengths."""
        axes = [tuple(seeds)]
        for a in (profiles, delta_r, beta):
            axes.append((None,) if a is None else tuple(a))
        rows = [tuple(reversed(row))
                for row in itertools.product(*reversed(axes))]
        cols = list(zip(*rows))
        return cls(
            seeds=tuple(cols[0]),
            profiles=None if profiles is None else tuple(cols[1]),
            delta_r=None if delta_r is None else tuple(cols[2]),
            beta=None if beta is None else tuple(cols[3]))

    def describe(self, e: int) -> Dict:
        """One experiment's coordinates as a plain dict (benchmark
        record metadata)."""
        out: Dict = {"seed": int(self.seeds[e])}
        if self.profiles is not None:
            out["profile"] = self.profiles[e]
        if self.delta_r is not None:
            out["delta_r"] = int(self.delta_r[e])
        if self.beta is not None:
            out["beta"] = float(self.beta[e])
        return out


def _stack_trees(trees):
    """Stack a list of identically-structured pytrees on a new leading
    (experiment) axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _pad_exp_nodes(tree, n_pad: int):
    """Edge-replicate the *second* (node) axis of every ``[E, n, ...]``
    leaf up to ``n_pad`` — the sweep twin of ``compiled._pad_nodes``.
    ``[E]``-shaped per-experiment scalars pass through."""
    def one(x):
        x = jnp.asarray(x)
        if x.ndim <= 1 or x.shape[1] >= n_pad:
            return x
        width = [(0, 0), (0, n_pad - x.shape[1])] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, width, mode="edge")
    return jax.tree_util.tree_map(one, tree)


class SweepSuperstep:
    """E experiments' compiled supersteps, vmapped into one scan.

    Construction (``E = len(spec)`` experiments, ``n = cfg.n_nodes``
    nodes each):

    * ``spec`` — the :class:`SweepSpec` experiment axis;
    * ``init_fn`` / ``loss_fn`` / ``eval_fn`` / ``optimizer`` — shared
      per-node functions, exactly the single engine's;
    * ``streams`` — one :class:`repro.data.DeviceDataStream` per
      experiment over one shared dataset (validated and stacked by
      :func:`repro.data.stack_streams`); each stream's own ``seed`` is
      the experiment's batch-draw seed;
    * ``strategies`` — one in-graph strategy per experiment.  All must
      be the same class; experiment 0's ``graph_round`` /
      ``sweep_graph_round`` is the traced control plane and the others
      contribute only their (per-seed) initial graph state.  When the
      spec carries ``delta_r``/``beta`` axes the strategy must expose
      ``sweep_graph_round`` (``InGraphMorphStrategy`` does);
    * ``cfg`` — shared :class:`RunnerConfig` (``rounds`` /
      ``eval_every`` / ``sim_every``; ``cfg.seed`` is superseded by
      ``spec.seeds``);
    * ``net`` — optional :class:`repro.netsim.SweepNetwork` (one
      :class:`DenseNetwork` per experiment, shared ``round_s``);
    * ``mesh`` — optional 2-D ``("exp", "data")`` mesh
      (:func:`repro.launch.mesh.make_sweep_mesh`); the experiment axis
      shards over ``"exp"`` (requires ``E % exp_devices == 0``), the
      node axis optionally over ``"data"`` (gather schedule, no-net
      sweeps only);
    * ``chunk`` / ``mix_chunk_d`` / ``eval_batch_chunk`` — the single
      engine's dispatch/memory knobs, unchanged semantics.

    Scope: the sweep axis covers the **dense gather path** — the
    configuration the bitwise conformance pin covers.  Sparse engines,
    Pallas kernels, compressed gossip and the psum collective are
    structural (they change the traced program per experiment) and stay
    single-experiment concerns.
    """

    def __init__(self, *, spec: SweepSpec, init_fn: Callable,
                 loss_fn: Callable, eval_fn: Callable,
                 optimizer: Optimizer,
                 streams: Sequence[DeviceDataStream],
                 test_batch: Dict[str, np.ndarray],
                 strategies: Sequence, cfg: RunnerConfig,
                 net=None, mesh=None, chunk: Optional[int] = None,
                 mix_chunk_d: Optional[int] = None,
                 eval_batch_chunk: Optional[int] = None):
        E = len(spec)
        if len(streams) != E:
            raise ValueError(f"{len(streams)} data streams for {E} "
                             "experiments")
        if len(strategies) != E:
            raise ValueError(f"{len(strategies)} strategies for {E} "
                             "experiments")
        if net is not None and len(net) != E:
            raise ValueError(f"SweepNetwork stacks {len(net)} profiles "
                             f"for {E} experiments")
        first = strategies[0]
        if not getattr(first, "in_graph", False):
            raise TypeError(
                f"strategy {getattr(first, 'name', first)!r} has no "
                "in-graph surface; the sweep engine vmaps graph_round")
        if getattr(first, "sparse", False):
            raise TypeError("sparse-native strategies are outside the "
                            "sweep axis (dense gather path only)")
        if any(type(s) is not type(first) for s in strategies):
            raise TypeError("all experiments must run the same strategy "
                            "class — experiment 0's graph_round is the "
                            "shared traced control plane")
        hp_axis = spec.delta_r is not None or spec.beta is not None
        if hp_axis and not hasattr(first, "sweep_graph_round"):
            raise TypeError(
                f"strategy {getattr(first, 'name', first)!r} has no "
                "sweep_graph_round; delta_r/beta sweep axes need the "
                "traced-hyperparameter surface (InGraphMorphStrategy)")
        for st in streams:
            if st.n != cfg.n_nodes:
                raise ValueError(f"data stream covers {st.n} nodes, "
                                 f"config says {cfg.n_nodes}")

        self.spec = spec
        self.cfg = cfg
        self.E = E
        self.strategy = first
        self.chunk = chunk
        self.log: List[MetricsLog] = [MetricsLog() for _ in range(E)]
        self.edge_history: List[list] = [[] for _ in range(E)]
        self.delivered_history: List[list] = [[] for _ in range(E)]
        self._comm_bytes = [0] * E
        self.test_batch = {k: jnp.asarray(v) for k, v in test_batch.items()}

        n = cfg.n_nodes
        # Per-experiment init, exactly the single engine's params=None
        # path with cfg.seed := spec.seeds[e], then stacked to [E, n, ...].
        per_exp_p, per_exp_o = [], []
        for e in range(E):
            keys = jax.random.split(jax.random.PRNGKey(spec.seeds[e]), n)
            p = jax.vmap(init_fn)(keys)
            per_exp_p.append(p)
            per_exp_o.append(jax.vmap(optimizer.init)(p))
        params = _stack_trees(per_exp_p)
        opt_state = _stack_trees(per_exp_o)
        self._model_bytes = cfg.model_bytes \
            or stacked_model_bytes(per_exp_p[0], n)

        # --- 2-D mesh layout ----------------------------------------------
        self.mesh = mesh
        if mesh is not None:
            if "exp" not in mesh.shape or "data" not in mesh.shape:
                raise ValueError("sweep mesh needs ('exp', 'data') axes — "
                                 "build it with launch.mesh.make_sweep_mesh")
            exp_shard = mesh.shape["exp"]
            node_shard = mesh.shape["data"]
            if E % exp_shard != 0:
                raise ValueError(f"E={E} experiments do not divide over "
                                 f"exp_devices={exp_shard}")
            if node_shard > 1 and net is not None:
                raise ValueError(
                    "the sweep's network model keeps its snapshot ring "
                    "per-experiment; node-axis sharding is a no-net "
                    "configuration (use exp_devices only)")
        else:
            exp_shard, node_shard = 1, 1
        self._node_shard = node_shard
        self.n_pad = math.ceil(n / node_shard) * node_shard
        n_local = self.n_pad // node_shard
        self._nspec = "data" if node_shard > 1 else None

        self._params = _pad_exp_nodes(params, self.n_pad)
        self._opt_state = _pad_exp_nodes(opt_state, self.n_pad)

        # --- stacked per-experiment operands (the vmapped `ex` pytree) ----
        data, index, sizes, dseeds, _batch = stack_streams(streams)
        stream0 = streams[0]
        ex: Dict[str, jnp.ndarray] = {
            "index": _pad_exp_nodes(jnp.asarray(index), self.n_pad),
            "sizes": _pad_exp_nodes(jnp.asarray(sizes), self.n_pad),
            "data_seed": jnp.asarray(dseeds),
        }
        if hp_axis:
            if spec.delta_r is not None:
                ex["delta_r"] = jnp.asarray(spec.delta_r, jnp.int32)
            if spec.beta is not None:
                ex["beta"] = jnp.asarray(spec.beta, jnp.float32)

        # --- per-experiment network model (DESIGN.md §9 folded over E) ----
        self.net = net
        self.net_stats: Optional[List[Dict]] = None
        if net is not None:
            S = net.depth(self._model_bytes)         # shared physical ring
            nseeds, fixed, jit_s, drop = net.profile_arrays(
                self._model_bytes)
            up_np, step_np = net.round_masks(cfg.rounds, n)
            ex.update(
                net_seed=jnp.asarray(nseeds),
                fixed=jnp.asarray(fixed),
                jitter=jnp.asarray(jit_s),
                drop=jnp.asarray(drop),
                depth=jnp.asarray(net.depths(self._model_bytes)),
                up=jnp.asarray(up_np),               # [E, rounds, n]
                step=jnp.asarray(step_np))
            hist = jax.tree_util.tree_map(
                lambda x: jnp.repeat(x[:, :, None], S, axis=2),
                self._params)
            lhist = jnp.full((E, n, S), -1, jnp.int32)
            self._netstate = (hist, lhist)
            self._net_S = S
            self.net_stats = [
                {"delivered": 0, "dropped": 0,
                 "staleness_hist": np.zeros(S, np.int64),
                 "staleness_sum": 0} for _ in range(E)]
        else:
            self._netstate = ()
            self._net_S = 0

        gstate = _stack_trees([s.init_graph_state() for s in strategies])
        needs_sim = bool(getattr(first, "needs_sim", False))
        uniform = bool(getattr(first, "uniform_mixing", False))
        self.gstate = gstate
        self.sim = jnp.zeros((E, n, n), jnp.float32)
        sim_fn = first.compute_sim if needs_sim else None

        local_step = make_local_step(loss_fn, optimizer)
        round_s = net.round_s if net is not None else 1.0
        S = self._net_S
        n_pad = self.n_pad
        sharded = mesh is not None

        def shard_index():
            return jax.lax.axis_index("data")

        def gather_full(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, "data", axis=0,
                                             tiled=True), tree)

        def embed_w(w):
            if n_pad == n:
                return w
            wp = jnp.zeros((n_pad, n_pad), w.dtype).at[:n, :n].set(w)
            tail = jnp.arange(n, n_pad)
            return wp.at[tail, tail].set(1)

        def graph_round(gstate_e, rnd, sim_e, ex_e):
            if hp_axis:
                return first.sweep_graph_round(
                    gstate_e, rnd, sim_e,
                    delta_r=ex_e.get("delta_r"), beta=ex_e.get("beta"))
            return first.graph_round(gstate_e, rnd, sim_e)

        def refresh_sim(rnd, params_logical, sim_e):
            # Unbatched predicate: under vmap this stays a real cond —
            # off-cadence rounds skip the Eq.-3 kernel entirely.
            return jax.lax.cond(
                rnd % cfg.sim_every == 0,
                lambda p, s: sim_fn(p).astype(jnp.float32),
                lambda p, s: s, params_logical, sim_e)

        def net_arrays(rnd, ex_e):
            # The single engine's net_masks, rebuilt from this
            # experiment's folded profile scalars: same clip / diag /
            # floor ops over the same keyed draws, so each experiment
            # sees bitwise its own DenseNetwork's matrices.
            r = jnp.minimum(rnd, cfg.rounds - 1)
            up, step = ex_e["up"][r], ex_e["step"][r]
            jit_m = sampling.jitter_matrix_folded(ex_e["net_seed"], rnd, n,
                                                  ex_e["jitter"])
            s = jnp.floor((ex_e["fixed"] + jit_m) / round_s)
            s = jnp.clip(s.astype(jnp.int32), 0, ex_e["depth"] - 1)
            stal = jnp.where(jnp.eye(n, dtype=bool), 0, s)
            drop = sampling.drop_matrix_folded(ex_e["net_seed"], rnd, n,
                                               ex_e["drop"])
            return up, step, stal, drop

        def net_mix(w_stal_flat, hist):
            flat = jax.tree_util.tree_map(
                lambda l: l.reshape((l.shape[0] * l.shape[1],)
                                    + l.shape[2:]), hist)
            return jax.tree_util.tree_map(
                lambda leaf: tensordot_mix_leaf(w_stal_flat, leaf,
                                                mix_chunk_d), flat)

        def exp_round(carry_e, rnd, ex_e):
            # One experiment's round at logical n — the single-device
            # round_body of dlrt.compiled with the per-experiment
            # operands threaded through `ex_e`.
            params, opt_state, gstate_e, sim_e, netstate = carry_e
            batch = stream0.draw(data, ex_e["index"], ex_e["sizes"],
                                 jnp.arange(n, dtype=jnp.int32), rnd,
                                 seed=ex_e["data_seed"])
            new_p, new_o = local_step(params, opt_state, batch)
            if net is None:
                params, opt_state = new_p, new_o
            else:
                up, step, stal, drop = net_arrays(rnd, ex_e)
                params = net_select(step, new_p, params)
                opt_state = net_select(step, new_o, opt_state)
            if sim_fn is not None:
                sim_e = refresh_sim(rnd, params, sim_e)
            gstate_e, edges, w = graph_round(gstate_e, rnd, sim_e, ex_e)
            if net is None:
                params = apply_mixing(w.astype(jnp.float32), params,
                                      chunk_d=mix_chunk_d)
                return (params, opt_state, gstate_e, sim_e, netstate), edges
            netstate = net_push(params, netstate, rnd, step, S)
            delivered, d_idx, w_stal, stale_counts = net_effective(
                edges, w, up, step, stal, drop, S, uniform=uniform)
            obs_sum = net_observed(rnd, netstate[1], d_idx, delivered)
            params = net_mix(w_stal.reshape(n, n * S), netstate[0])
            return (params, opt_state, gstate_e, sim_e, netstate), \
                (edges, delivered, stale_counts, obs_sum)

        def exp_round_node_sharded(carry_e, rnd, ex_e):
            # One experiment's round with the node axis split over
            # "data" — the gather schedule of round_body_sharded, per
            # experiment (no-net only).
            params, opt_state, gstate_e, sim_e, netstate = carry_e
            ids = shard_index() * n_local \
                + jnp.arange(n_local, dtype=jnp.int32)
            batch = stream0.draw(data, ex_e["index"], ex_e["sizes"], ids,
                                 rnd, seed=ex_e["data_seed"])
            params, opt_state = local_step(params, opt_state, batch)
            full = gather_full(params)
            if sim_fn is not None:
                logical = jax.tree_util.tree_map(lambda x: x[:n], full)
                sim_e = refresh_sim(rnd, logical, sim_e)
            gstate_e, edges, w = graph_round(gstate_e, rnd, sim_e, ex_e)
            w_rows = jax.lax.dynamic_slice_in_dim(
                embed_w(w.astype(jnp.float32)), shard_index() * n_local,
                n_local, 0)
            params = jax.tree_util.tree_map(
                lambda leaf: tensordot_mix_leaf(w_rows, leaf, mix_chunk_d),
                full)
            return (params, opt_state, gstate_e, sim_e, netstate), edges

        body = exp_round_node_sharded if node_shard > 1 else exp_round

        def superstep(carry, rnds, data_arg, ex_arg):
            def step(c, rnd):
                def one(ce, exe):
                    return body(ce, rnd, exe)
                return jax.vmap(one)(c, ex_arg)
            return jax.lax.scan(step, carry, rnds)

        # `data` rides as an explicit jit argument (replicated under
        # sharding), not a closure constant, so the shared dataset is
        # never baked into the jaxpr.
        self._data = data = jax.tree_util.tree_map(jnp.asarray, data)
        self._ex = ex

        if sharded:
            def leaf_spec(x):
                nd = getattr(x, "ndim", 0)
                if nd >= 2 and x.shape[0] == E and x.shape[1] == n_pad \
                        and node_shard > 1:
                    return P("exp", "data")
                if nd >= 1 and x.shape[0] == E:
                    return P("exp")
                return P()
            exp_nodes = P("exp", self._nspec)
            ex_specs = {k: P("exp") for k in ex}
            ex_specs["index"] = exp_nodes
            ex_specs["sizes"] = exp_nodes
            carry_specs = (
                jax.tree_util.tree_map(leaf_spec, self._params),
                jax.tree_util.tree_map(leaf_spec, self._opt_state),
                jax.tree_util.tree_map(lambda _: P("exp"), gstate),
                P("exp"),
                jax.tree_util.tree_map(lambda _: P("exp"),
                                       self._netstate))
            data_specs = jax.tree_util.tree_map(lambda _: P(), data)
            # ys stack as [K(rounds), E, ...] under the scan, so the
            # experiment axis is axis 1, not 0.
            ys_spec = P(None, "exp")
            ys_specs = ys_spec if net is None \
                else (ys_spec, ys_spec, ys_spec, ys_spec)
            self._superstep = jax.jit(jax.shard_map(
                superstep, mesh=mesh,
                in_specs=(carry_specs, P(), data_specs, ex_specs),
                out_specs=(carry_specs, ys_specs), check_vma=False))
            put = lambda spec: lambda x: jax.device_put(
                x, NamedSharding(mesh, spec))
            self._params = jax.tree_util.tree_map(
                lambda x: put(leaf_spec(x))(x), self._params)
            self._opt_state = jax.tree_util.tree_map(
                lambda x: put(leaf_spec(x))(x), self._opt_state)
            self._ex = {k: put(ex_specs[k])(v) for k, v in ex.items()}
            self._data = jax.tree_util.tree_map(put(P()), data)
        else:
            self._superstep = jax.jit(superstep)

        self._evaluate = jax.jit(jax.vmap(
            make_evaluator(eval_fn, batch_chunk=eval_batch_chunk),
            in_axes=(0, None)))

    # ------------------------------------------------------------------

    @property
    def params(self):
        """Per-experiment node-stacked parameters, logical
        ``[E, n, ...]`` view."""
        if self.n_pad == self.cfg.n_nodes:
            return self._params
        return jax.tree_util.tree_map(
            lambda x: x[:, :self.cfg.n_nodes], self._params)

    @property
    def opt_state(self):
        """Optimizer state, logical ``[E, n, ...]`` view."""
        if self.n_pad == self.cfg.n_nodes:
            return self._opt_state
        return jax.tree_util.tree_map(
            lambda x: x[:, :self.cfg.n_nodes]
            if getattr(x, "ndim", 0) >= 2 and x.shape[1] == self.n_pad
            else x, self._opt_state)

    def compiled_hlo(self, chunk: Optional[int] = None,
                     start: int = 0) -> str:
        """Compile — without executing — one ``chunk``-round sweep
        superstep and return its post-optimization HLO text (the
        autotuner / benchmark-gate surface, like
        ``CompiledSuperstep.compiled_hlo``)."""
        k = chunk or self.chunk or self.cfg.eval_every
        rnds = jnp.arange(start, start + k)
        carry = (self._params, self._opt_state, self.gstate, self.sim,
                 self._netstate)
        lowered = self._superstep.lower(carry, rnds, self._data, self._ex)
        return lowered.compile().as_text()

    def _run_chunk(self, start: int, end: int) -> np.ndarray:
        """Execute rounds ``[start, end]`` for every experiment as one
        dispatch; decode the stacked ``[K, E, ...]`` round outputs into
        the per-experiment histories.  Returns the ``[K, E, n, n]``
        negotiated-edge stack."""
        rnds = jnp.arange(start, end + 1)
        carry = (self._params, self._opt_state, self.gstate, self.sim,
                 self._netstate)
        carry, ys = self._superstep(carry, rnds, self._data, self._ex)
        (self._params, self._opt_state, self.gstate, self.sim,
         self._netstate) = carry
        # The per-experiment reductions run vectorized over the E axis
        # (one numpy call each, not E) — at chunk=1 a per-experiment
        # Python loop of sums would rival the dispatch itself.
        if self.net is None:
            edges_np = np.asarray(ys, bool)              # [K, E, n, n]
            edge_sums = edges_np.sum(axis=(0, 2, 3))     # [E]
            for e in range(self.E):
                self.edge_history[e].extend(edges_np[:, e])
                self._comm_bytes[e] += int(edge_sums[e]) \
                    * self._model_bytes
            return edges_np
        edges_stack, delivered_stack, stale_stack, obs_stack = ys
        edges_np = np.asarray(edges_stack, bool)
        delivered_np = np.asarray(delivered_stack, bool)
        stale_np = np.asarray(stale_stack, np.int64)     # [K, E, S]
        obs_np = np.asarray(obs_stack, np.int64)         # [K, E]
        edge_sums = edges_np.sum(axis=(0, 2, 3))         # [E]
        del_sums = delivered_np.sum(axis=(0, 2, 3))      # [E]
        stale_sums = stale_np.sum(axis=0)                # [E, S]
        obs_sums = obs_np.sum(axis=0)                    # [E]
        for e in range(self.E):
            self.edge_history[e].extend(edges_np[:, e])
            self.delivered_history[e].extend(delivered_np[:, e])
            n_del = int(del_sums[e])
            self._comm_bytes[e] += n_del * self._model_bytes
            st = self.net_stats[e]
            st["delivered"] += n_del
            st["dropped"] += int(edge_sums[e]) - n_del
            st["staleness_hist"] += stale_sums[e]
            st["staleness_sum"] += int(obs_sums[e])
        return edges_np

    def staleness_mean(self, e: int) -> float:
        """Experiment ``e``'s mean delivered content-staleness in rounds
        (0.0 without a network model)."""
        if self.net_stats is None:
            return 0.0
        return net_staleness_mean(self.net_stats[e])

    def comm_bytes(self, e: int) -> int:
        """Experiment ``e``'s cumulative communication bytes."""
        return self._comm_bytes[e]

    def evaluate(self, rnd: int, edges: np.ndarray) -> List[RoundRecord]:
        """Evaluate every experiment's population on the shared test set
        after round ``rnd`` and append one §IV-A4 :class:`RoundRecord`
        per experiment (``edges``: the ``[E, n, n]`` final-round
        stack)."""
        losses, metrics = self._evaluate(self.params, self.test_batch)
        losses = np.asarray(losses)
        metrics = {k: np.asarray(v) for k, v in metrics.items()}
        recs = []
        for e in range(self.E):
            rec = make_round_record(
                rnd, losses[e], {k: v[e] for k, v in metrics.items()},
                self._comm_bytes[e], edges[e])
            self.log[e].add(rec)
            recs.append(rec)
        return recs

    def run(self, progress: Optional[Callable] = None
            ) -> List[MetricsLog]:
        """Run all ``cfg.rounds`` rounds for every experiment in
        eval-boundary-aligned sweep supersteps; returns one
        :class:`MetricsLog` per experiment (``progress``, if given, is
        invoked with each boundary's record list)."""
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
            recs = self.evaluate(end, edges_np[-1])
            if progress is not None:
                progress(recs)
        return self.log

    def run_steps(self, rounds: int, chunk: Optional[int] = None) -> None:
        """Throughput mode: ``rounds`` rounds for every experiment in
        fixed-size supersteps, no evaluation — the fig14 benchmark loop."""
        chunk = chunk or self.chunk or rounds
        start = 0
        while start < rounds:
            end = min(start + chunk, rounds) - 1
            self._run_chunk(start, end)
            start = end + 1
