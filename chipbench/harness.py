"""One run of one benchmark cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (its file under ``configs``: the node model's sizes and
its ``family``) and a traffic mix (``chipbench/traffic/<traffic>.json``:
strategy, population, degree, data split, batch, cadence), and has its
limits of ``correct`` in ``chipbench/limits/<cell>.json``.  Per-layer
metrics are read by ``chipbench/metrics/<metric>.py``.  The node model's
family is ``chipbench/families/<family>.py`` with its reference half
``<family>_reference.py``, found under the cell's own checkout.  A new
cell, mix, metric or family is a new file; nothing here names one.

A family's file supplies

* ``build(seed, model, traffic) -> (train, parts, test)``: the data,
  made on the device from the seed; ``parts`` are the per-node index
  lists of the shared split (``chipbench/data.py``);
* ``node(model) -> (init_fn, loss_fn, eval_fn)``, built from the
  program's own modules, with the reference half's ``init_node`` as the
  start, and ``batcher(train, parts, traffic, stream_seed)``, what
  ``DecentralizedRunner`` is handed;
* ``param_count(model)``, ``round_flops(model, traffic, edges,
  negotiates)`` and ``eval_flops(model, traffic)``: model FLOPs from
  shapes alone, multiply-adds times two, only the work a round requires.
  The rules every family shares: the local step of every node on its
  batch; mixing ``2 (edges + n) D`` a round, the edges in use that round
  plus each node's own model; one Eq.-3 Gram ``2 n^2 D`` a negotiating
  round (the strategy reads the similarity only when it negotiates);
  every node's forward over the test set an evaluation.

Its reference half supplies ``init_node(key, model)``,
``loss_and_accuracy(p, batch, model)`` in plain ``jax.numpy`` at
``Precision.HIGHEST``, and ``draw(train, rows, sizes, key, rnd, batch)``,
the batches the program's stream draws; it imports nothing of the
program.

The run is built as a user builds it: ``DecentralizedRunner`` with
``RunnerConfig(compiled=True)``, the family's node and batcher and
``sgd(lr)``; a cell on four chips shards its population over them
(``RunnerConfig(mesh_devices=4)``), the reference stays on one device.
``DecentralizedRunner.run`` drives the compiled engine's segments: a
superstep of ``eval_every`` rounds, then ``evaluate`` on the test set.

* Set-up: data, weights and runner; round 0 (its own K = 1 superstep)
  and its evaluation; the first whole segment.  These are the rounds the
  reference follows.
* Window: whole segments from there, with round indices running on,
  until the first segment end after ``seconds``.  ``cfg.rounds`` is a
  multiple of ``eval_every`` that no window reaches, so no shorter last
  chunk ever compiles.  A compile inside the window voids the run.
* With ``trace``, the profiler records a few whole segments inside the
  window and the per-layer metrics come from that trace.
* After the window: peak device memory, then the program's state is
  freed and the reference runs.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
# Far beyond any window: a multiple of eval_every, so the short last
# chunk that eval_boundaries adds at rounds - 1 is never reached.
SEGMENTS_PLANNED = 4000
TRACE_SKIP = 2            # window segments before the profiler starts
TRACE_SEGMENTS = 3        # whole segments traced
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
STREAM_SEED = 0
_MODULES = {}             # path -> module, see load_module


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix, limits (None if it has none yet) and the
    per-layer metrics it reports."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; the benchmark has "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    limits = root / "chipbench" / "limits" / f"{name}.json"
    return {
        "name": name, "chips": cell["chips"],
        "model": load_json(root / configs[cell["config"]]["file"]),
        "traffic": load_json(root / "chipbench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "limits": load_json(limits) if limits.exists() else None,
        "per_layer": [m["name"] for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "root": root,
    }


def load_module(path: Path):
    """The Python file at ``path`` as a module, run once per process: a
    family's file and the reference share one reference half."""
    path = Path(path).resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"chipbench.{path.parent.name}.{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_family(name: str, root: Path = ROOT) -> tuple:
    """The node-model family ``name`` of the checkout at ``root``: its
    file ``chipbench/families/<name>.py`` and its reference half
    ``<name>_reference.py``."""
    folder = root / "chipbench" / "families"
    return (load_module(folder / f"{name}.py"),
            load_module(folder / f"{name}_reference.py"))


def cell_family(cell: dict) -> tuple:
    """:func:`load_family` of the cell's configuration."""
    return load_family(cell["model"]["family"], cell["root"])


def build_data(cell: dict, seeds: dict):
    """The cell's ``(train, parts, test)``, made by its family."""
    return cell_family(cell)[0].build(seeds["data"], cell["model"],
                                      cell["traffic"])


def use_checkout_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at the fixed path
    ``<root>/.jax_cache``, every program in it however small, so that
    only a cell's first run in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def sub_seeds(seed: int) -> dict:
    """31-bit seeds of the data, the weights and the strategy, from any
    whole-number ``seed``, and the seed of the batch draws, which is
    ``STREAM_SEED`` for every run: the program's device data stream
    writes its seed into the compiled superstep as a constant, so a seed
    of its own per run would compile the superstep anew in every run.
    The batches still differ from seed to seed, drawn from other data
    and another split."""
    words = np.random.SeedSequence(abs(int(seed))).generate_state(3)
    out = {name: int(w) & 0x7FFFFFFF for name, w in
           zip(("data", "init", "strategy"), words)}
    return dict(out, stream=STREAM_SEED)


class CompileCounter:
    """Count of XLA compiles (persistent-cache loads among them) and of
    persistent-cache hits, from JAX's own compile events."""

    def __init__(self):
        import jax
        self.count = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.count += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1


def make_runner(cell: dict, seeds: dict, train, parts, test):
    """The cell's ``DecentralizedRunner``, built as a user builds it."""
    from repro.core import InGraphEpidemicStrategy, InGraphMorphStrategy
    from repro.dlrt import DecentralizedRunner, RunnerConfig
    from repro.optim import sgd

    family, _ = cell_family(cell)
    t = cell["traffic"]
    n = t["nodes"]
    if t["strategy"] == "morph":
        strategy = InGraphMorphStrategy(n=n, k=t["k"], view_size=t["k"] + 2,
                                        delta_r=t["delta_r"],
                                        seed=seeds["strategy"])
    elif t["strategy"] == "epidemic":
        strategy = InGraphEpidemicStrategy(n=n, k=t["k"],
                                           seed=seeds["strategy"])
    else:
        raise ValueError(f"unknown strategy {t['strategy']!r}")
    init, loss, evaluate = family.node(cell["model"])
    return DecentralizedRunner(
        init_fn=init, loss_fn=loss, eval_fn=evaluate,
        optimizer=sgd(t["lr"]),
        batcher=family.batcher(train, parts, t, seeds["stream"]),
        test_batch=test, strategy=strategy,
        cfg=RunnerConfig(
            n_nodes=n, rounds=SEGMENTS_PLANNED * t["eval_every"],
            eval_every=t["eval_every"], seed=seeds["init"], compiled=True,
            eval_batch_chunk=t["eval_chunk"],
            mesh_devices=cell["chips"] if cell["chips"] > 1 else None))


def segment_failed(traffic: dict, rec, edges) -> bool:
    """A segment fails on a non-finite loss, an accuracy outside [0, 1], or
    a round whose edges break the strategy's degree bound: for Morph
    in-degree in [1, k] and out-degree at most k; for Epidemic every node
    sends to exactly k peers; for both, no node to itself."""
    acc = np.asarray(rec.per_node_accuracy)
    if not math.isfinite(rec.mean_loss) or (acc < 0).any() or (acc > 1).any():
        return True
    k = min(traffic["k"], traffic["nodes"] - 1)
    for e in edges:
        e = np.asarray(e, bool)
        deg_in, deg_out = e.sum(axis=1), e.sum(axis=0)
        if e.diagonal().any():
            return True
        if traffic["strategy"] == "morph":
            if (deg_in < 1).any() or (deg_in > k).any() or (deg_out > k).any():
                return True
        elif (deg_out != k).any():
            return True
    return False


class WindowClosed(Exception):
    """Raised from the progress callback to end ``run`` at a segment
    end."""


class Window:
    """The progress callback: set-up until the first whole segment ends,
    then the measured window, with the traced segments inside it
    (``seconds=None``: no window, stop at the end of set-up)."""

    def __init__(self, runner, cell, seconds, trace, t0, clock):
        self.runner, self.cell, self.seconds = runner, cell, seconds
        self.trace, self.t0, self.clock = trace, t0, clock
        self.every = cell["traffic"]["eval_every"]
        self.records = []
        self.marks = []             # (set-up stage, end time)
        self.snap = {}
        self.start = self.end = None
        self.setup_s = None
        self.segments = self.failed = 0
        self.compiles_before = None
        self.trace_dir = None
        self.traced = None          # (first, last) window segment traced
        self._spans = []

    def _span(self, name):
        import jax
        span = jax.profiler.TraceAnnotation(name)
        span.__enter__()
        self._spans.append(span)

    def __call__(self, rec):
        import jax
        now = time.perf_counter()
        engine = self.runner.engine
        self.records.append(rec)
        if rec.rnd == 0:
            self.snap["p1"] = jax.device_get(engine.params)
            self.marks.append(("round 0", time.perf_counter()))
            return
        if rec.rnd == self.every:
            self.snap["p_end"] = jax.device_get(engine.params)
            self.snap["edges"] = np.stack(engine.edge_history)
            if self.seconds is None:
                raise WindowClosed
            self.marks.append(("first segment", now))
            self.setup_s = now - self.t0
            self.compiles_before = self.clock.count
            self.start = time.perf_counter()
            return
        self.segments += 1
        self.failed += segment_failed(self.cell["traffic"], rec,
                                      engine.edge_history[-self.every:])
        if self.trace:
            self._trace_step()
        elapsed = time.perf_counter() - self.start
        if elapsed >= self.seconds and (not self.trace
                                        or self.traced is not None):
            self.end = time.perf_counter()
            raise WindowClosed

    def _trace_step(self):
        import jax
        seg, first = self.segments, TRACE_SKIP
        if seg == first:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.trace_dir)
            self._span("chipbench.traced")
        elif first < seg <= first + TRACE_SEGMENTS:
            self._spans.pop().__exit__(None, None, None)
            if seg == first + TRACE_SEGMENTS:
                self._spans.pop().__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.traced = (first + 1, seg)
                return
        else:
            return
        self._span(f"chipbench.segment {seg + 1}")


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def peak_lookup(kind: str):
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return lambda key: float(table[kind][key])


def read_metric(name: str, ctx: dict):
    return load_module(HERE / "metrics" / f"{name}.py").read(ctx)


def traced_flops(cell, runner, first, last) -> float:
    """Model FLOPs of window segments ``first..last`` (1-based)."""
    family, _ = cell_family(cell)
    t, every = cell["traffic"], cell["traffic"]["eval_every"]
    total = 0.0
    for seg in range(first, last + 1):
        r0 = every * seg + 1              # segment 1: rounds every+1..2every
        for rnd in range(r0, r0 + every):
            edges = int(np.asarray(runner.engine.edge_history[rnd]).sum())
            total += family.round_flops(
                cell["model"], t, edges,
                negotiates=t["strategy"] == "morph"
                and rnd % t["delta_r"] == 0)
        total += family.eval_flops(cell["model"], t)
    return total


def layer_context(tr, window, chips, stages=None, **counts) -> dict:
    """What a per-layer reader is handed: the trace cut to the cell's own
    ``chips`` devices (the machine may show more), the traced window, the
    cell's chip count, ``stages``, the superstep's ``{instruction name:
    stage}`` (:func:`chipbench.trace.op_stages`; empty where not given),
    and ``counts`` (``rounds``, ``evals``, ``flops``, ``peak``)."""
    return dict(counts, trace=dict(tr, devices=tr["devices"][:chips]),
                window=window, chips=chips, stages=stages or {})


def superstep_stages(runner) -> dict:
    """``{instruction name: stage}`` of the window's superstep, from its
    post-optimization HLO (compiled again after the window, which the
    persistent cache serves); the stages are the program's own scope
    names, ``repro.dlrt.compiled.STAGES``."""
    from repro.dlrt.compiled import STAGES

    from chipbench import trace
    return trace.op_stages(runner.engine.compiled_hlo(), STAGES)


def per_layer(cell, runner, win) -> tuple:
    """The per-layer metrics, ``device`` additions and the breakdown,
    from the traced segments and the superstep's stage map."""
    import jax

    from chipbench import trace
    stages = superstep_stages(runner)
    tr = trace.load(win.trace_dir)
    shutil.rmtree(win.trace_dir, ignore_errors=True)
    span = trace.host_span(tr["host"], "chipbench.traced")
    if span is None or not tr["devices"]:
        raise RuntimeError("the trace holds no traced span or no device")
    lo, hi = span
    first, last = win.traced
    every = cell["traffic"]["eval_every"]
    kind = jax.devices()[0].device_kind
    ctx = layer_context(tr, (lo, hi), cell["chips"], stages,
                        rounds=every * (last - first + 1),
                        evals=last - first + 1,
                        flops=traced_flops(cell, runner, first, last),
                        peak=peak_lookup(kind))
    metrics = {}
    units = {m["name"]: m["unit"] for m in load_json(
        cell["root"] / "BENCHMARK.json")["per_layer"]}
    for name in cell["per_layer"]:
        value = read_metric(name, ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    devs = ctx["trace"]["devices"]
    busy = sum(trace.busy_ns(d, lo, hi) for d in devs) / len(devs) / 1e9
    times = [trace.stage_times(d, stages, lo, hi) for d in devs]
    total = sum(sum(t.values()) for t in times)
    unstaged = sum(t.get(None, 0) for t in times)
    print(f"stages: {1 - unstaged / max(total, 1):.4%} of the superstep's "
          "op time has a stage", file=sys.stderr)
    breakdown = {"device_ops": trace.top_ops(devs, lo, hi, stages=stages),
                 "idle_gaps": trace.idle_gaps(devs[0], tr["host"], lo, hi)}
    return metrics, {"busy_s": busy, "window_s": (hi - lo) / 1e9}, breakdown


def drive(runner, cell, seconds, trace=False, t0=0.0, clock=None):
    """``DecentralizedRunner.run`` under a :class:`Window`; returns it."""
    import jax
    win = Window(runner, cell, seconds, trace, t0, clock)
    try:
        with jax.profiler.TraceAnnotation("chipbench.run"):
            runner.run(win)
    except WindowClosed:
        pass
    return win


def program_summary(cell, p0, win):
    """What the program produced over the compared rounds."""
    from chipbench import compare
    rec0, rec_end = win.records[:2]
    return compare.summarise(
        p0, win.snap["p1"], win.snap["p_end"], win.snap["edges"],
        [(rec0.mean_loss, rec0.per_node_accuracy),
         (rec_end.mean_loss, rec_end.per_node_accuracy)],
        cell["traffic"]["lr"])


def reference_summary(cell, seeds, train, parts, test, dtype=None,
                      block=None):
    """The reference's run over the compared rounds: ``(summary, the
    norms of its round-0 gradients, the start)``.  ``block`` forces the
    reference's nodes a block (tests)."""
    import jax.numpy as jnp

    from chipbench import compare, reference
    t = cell["traffic"]
    ref = reference.run(seeds=seeds, family=cell_family(cell)[1],
                        model=cell["model"], traffic=t,
                        train=train, parts=parts, test=test,
                        rounds=t["eval_every"] + 1,
                        dtype=jnp.float32 if dtype is None else dtype,
                        block=block)
    print(f"reference: {ref['block']} of {t['nodes']} nodes a block",
          file=sys.stderr)
    summary = compare.summarise(
        ref["p0"], ref["p1"], ref["p_end"], ref["edges"],
        [(losses.mean(), acc) for losses, acc in ref["evals"]], t["lr"])
    return summary, ref["grad0"], ref["p0"]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """One run: the result line's object, without a chip check (the entry
    point makes that)."""
    import jax

    from chipbench import compare

    if cell["limits"] is None:
        raise RuntimeError(f"no limits for {cell['name']}: "
                           f"chipbench/limits/{cell['name']}.json")
    clock = CompileCounter()
    seeds = sub_seeds(seed)
    marks = [("start", t0), ("backend", time.perf_counter())]
    with jax.profiler.TraceAnnotation("chipbench.setup"):
        train, parts, test = build_data(cell, seeds)
        jax.block_until_ready(train)
        marks.append(("data", time.perf_counter()))
        runner = make_runner(cell, seeds, train, parts, test)
        marks.append(("runner", time.perf_counter()))
    win = drive(runner, cell, seconds, trace, t0, clock)
    marks += win.marks
    stages = ", ".join(f"{name} {b - a:.3f} s" for (_, a), (name, b)
                       in zip(marks, marks[1:]))
    print(f"set-up: {stages}; {win.compiles_before} compiles, "
          f"{clock.hits} persistent-cache hits", file=sys.stderr)
    compiles = clock.count - win.compiles_before
    if compiles:
        raise RuntimeError(f"{compiles} compiles inside the measured "
                           "window: the run is void")
    device = device_info(cell["chips"])
    print(f"memory_stats: {jax.devices()[0].memory_stats()}",
          file=sys.stderr)
    every = cell["traffic"]["eval_every"]
    rounds = win.segments * every
    if trace:
        metrics, extra, breakdown = per_layer(cell, runner, win)
        device.update(extra)
    else:
        window_s = win.end - win.start
        values = {"setup_s": win.setup_s,
                  "round_ms": window_s * 1000.0 / rounds,
                  "peak_hbm_gb": device["memory_peak_bytes"] / 1e9}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        breakdown = None
    del runner, win.runner
    gc.collect()
    t_ref = time.perf_counter()
    ref, grad0, p0 = reference_summary(cell, seeds, train, parts, test)
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    nums = compare.numbers(program_summary(cell, p0, win), ref, grad0)
    ok, lines = compare.judge(nums, cell["limits"])
    result = {"correct": bool(ok and win.failed == 0),
              "attempted": win.segments, "failed": win.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": nums[k],
                              "limit": cell["limits"][k]}
                          for k in compare.NAMES
                          if cell["limits"].get(k) is not None}
    result["_lines"] = lines
    return result
