"""Reduction of a profiler trace (``.xplane.pb``) to device times.

:func:`load` turns the file into plain data: per device, its op events
and its program (module) events; and the host's events.  Every other
function here works on that plain data, so a test can hand them a
synthetic trace.  An event is ``(name, start_ns, end_ns, detail)``, where
``detail`` is the op's JAX name-stack path where the trace carries one.

All times are clipped to a window ``(lo, hi)`` in the trace's clock,
which the caller takes from its own host span around the traced
segments.

A device's op line nests: a ``while`` (the superstep's scan) or a
``conditional`` spans the ops of its body.  Busy time is the union of all
of them; the op ranking uses the innermost ops alone (:func:`leaves`).

On the TPU an op event carries no name stack, so an op's stage comes
from the compiled superstep's HLO text instead (:func:`op_stages`): the
trace names each op by its HLO instruction, and the instruction's
``op_name`` metadata holds the stage's scope.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int, str]
SUPERSTEP = "jit_superstep"           # the trace's name of the program
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPERAND = re.compile(r"%([\w.\-]+)")
OPCODE = re.compile(r"\s([\w\-]+)\(")
# Instructions that run no op of their own on the device: they neither
# take a stage from their neighbours nor pass one on.
NO_OP = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}
# A path component is a scope, or a scope wrapped by the transforms
# applied inside it: ``vmap(mix)``, ``transpose(jvp(local_step))``.
SCOPE = re.compile(r"(?:\w+\()*(\w+)\)*")


def load(log_dir: str) -> dict:
    """``{"devices": [{"name", "ops", "modules"}], "host": [events]}``
    from the one ``.xplane.pb`` under ``log_dir``; ``host`` holds the
    events of the host thread that recorded the span
    ``chipbench.traced``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"want one trace file under {log_dir}, "
                           f"found {len(files)}")
    data = ProfileData.from_file(files[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices.append({
                "name": plane.name,
                "ops": [_event(e) for e in lines["XLA Ops"].events],
                "modules": [_event(e) for e in lines["XLA Modules"].events]
                if "XLA Modules" in lines else []})
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                events = [_event(e, ln.name) for e in ln.events]
                if any(e[0] == "chipbench.traced" for e in events):
                    host = events
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def _event(e, thread: str = "") -> Event:
    """A TPU op event's name is its HLO text, ``%fusion.3 = f32[...]
    fusion(...), kind=...``: the name is the part before `` = ``, the
    detail the JAX name-stack path where a stat gives one, else the rest
    of the text (shapes and op kind), cut to 160 characters."""
    start = int(e.start_ns)
    name, _, rest = e.name.partition(" = ")
    detail = thread or rest[:160]
    if not thread:
        for key, value in e.stats:
            if key in ("tf_op", "name_stack") and value:
                detail = str(value)
    return (name.lstrip("%"), start, start + int(e.duration_ns), detail)


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that hold no other op: on a line of properly nested
    events sorted by start, an op is a leaf when the next op starts at or
    after its end."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= e[2]]


def merge(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint intervals."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def busy_ns(device: dict, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` in which some op ran on the device."""
    return length(merge([(a, b) for _, a, b, _ in device["ops"]], lo, hi))


def module_ns(device: dict, prefix: str, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` spent in programs whose name starts
    with ``prefix`` (the trace names a jitted ``f`` ``jit_f(<id>)``)."""
    return length(merge([(a, b) for name, a, b, _ in device["modules"]
                         if name.startswith(prefix)], lo, hi))


def _unwrap(part: str) -> str:
    m = SCOPE.fullmatch(part)
    return m.group(1) if m else part


def _stage_path(op_name: str, stages: Sequence[str]) -> Optional[str]:
    """The scope path ``op_name`` from the first of ``stages`` after its
    first ``while/body/`` (the superstep's scan) on, that stage's name
    unwrapped (``vmap(mix)/dot_general`` -> ``mix/dot_general``); None
    where no stage is on the path."""
    _, found, tail = op_name.partition("while/body/")
    parts = tail.split("/") if found else []
    for i, part in enumerate(parts):
        if _unwrap(part) in stages:
            return "/".join([_unwrap(part)] + parts[i + 1:])
    return None


def stage_of(path: Optional[str]) -> Optional[str]:
    """The stage of a stage path (:func:`op_stages`), or None."""
    return path.split("/")[0] if path else None


def op_stages(hlo: str, stages: Sequence[str]) -> Dict[str, str]:
    """``{instruction name: stage path}`` of a compiled program's HLO
    text: the instruction's ``op_name`` scope path from its stage on
    (:func:`_stage_path`), so ``local_step/transpose(jvp(forward))/...``
    is in stage ``local_step`` and a reader may look deeper.

    An instruction the compiler added with no metadata at all, such as a
    layout copy, takes the path of its first reader in the same
    computation that has one, else of its first operand that has one
    (:data:`NO_OP` instructions neither take nor pass a path).
    Instructions with metadata but no stage on it, such as the scan's
    counter or the program's entry, get none."""
    out: Dict[str, str] = {}
    computation: List[Tuple[str, str]] = []
    for line in hlo.splitlines() + ["}"]:
        m = INSTRUCTION.match(line)
        if m:
            computation.append((m.group(1), m.group(2)))
            continue
        if line.startswith("}"):
            out.update(_computation_stages(computation, stages))
            computation = []
    return out


def _computation_stages(instructions, stages) -> Dict[str, str]:
    """:func:`op_stages` of one computation's ``[(name, text)]``."""
    names = {name for name, _ in instructions}
    own, bare = {}, []
    operands: Dict[str, List[str]] = {}
    readers: Dict[str, List[str]] = {}
    for name, text in instructions:
        m = OP_NAME.search(text)
        if m:
            path = _stage_path(m.group(1), stages)
            if path is not None:
                own[name] = path
        elif "metadata={" not in text:
            op = OPCODE.search(" " + text)
            if op is None or op.group(1) not in NO_OP:
                bare.append(name)
        operands[name] = [x for x in OPERAND.findall(text.split(
            ", metadata={")[0]) if x in names and x != name]
        for x in operands[name]:
            readers.setdefault(x, []).append(name)
    changed = True
    while changed:
        changed = False
        for name in bare:
            if name in own:
                continue
            for x in readers.get(name, []) + operands[name]:
                if x in own:
                    own[name] = own[x]
                    changed = True
                    break
    return own


def _program_at(device: dict):
    """A function of a time giving the name of the program (module) that
    runs on ``device`` then, or None."""
    mods = sorted((a, b, name) for name, a, b, _ in device["modules"])
    starts = [a for a, _, _ in mods]

    def at(t: int) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else None
    return at


def _in_scope(path: Optional[str], scope: Sequence[str]) -> bool:
    """Whether the stage path's components, unwrapped, begin with
    ``scope``'s."""
    parts = [_unwrap(p) for p in path.split("/")] if path else []
    return parts[:len(scope)] == list(scope)


def scope_ns(device: dict, stages: Dict[str, str], scope: str, lo: int,
             hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` spent in the superstep's innermost ops
    whose stage path (``stages``, :func:`op_stages` of the superstep)
    begins with ``scope``: a stage (``mix``) or a scope within one
    (``local_step/forward``)."""
    want = scope.split("/")
    return sum(b - a for name, a, b in _superstep_ops(device, lo, hi)
               if _in_scope(stages.get(name), want))


def stage_times(device: dict, stages: Dict[str, str], lo: int, hi: int
                ) -> Dict[Optional[str], int]:
    """``{stage: nanoseconds}`` of ``[lo, hi]`` spent in the superstep's
    innermost ops, each under its stage by ``stages``; ops with none under
    None."""
    out: Dict[Optional[str], int] = {}
    for name, a, b in _superstep_ops(device, lo, hi):
        stage = stage_of(stages.get(name))
        out[stage] = out.get(stage, 0) + b - a
    return out


def _superstep_ops(device: dict, lo: int, hi: int):
    """``(name, start, end)`` of the superstep's innermost ops, clipped to
    ``[lo, hi]``."""
    at = _program_at(device)
    for name, a, b, _ in leaves(device["ops"]):
        a, b = max(a, lo), min(b, hi)
        prog = at(a)
        if b > a and prog is not None and prog.startswith(SUPERSTEP):
            yield name, a, b


def scope_ms_per_round(ctx: dict, scope: str) -> Optional[float]:
    """Device milliseconds a round in the superstep's ops in ``scope``
    (:func:`scope_ns`), mean over the cell's devices; None where the trace
    has no device or the stage map (``ctx["stages"]``) holds no op in the
    scope."""
    devs, stages = ctx["trace"]["devices"], ctx.get("stages") or {}
    want = scope.split("/")
    if not devs or not any(_in_scope(p, want) for p in stages.values()):
        return None
    lo, hi = ctx["window"]
    ns = [scope_ns(d, stages, scope, lo, hi) for d in devs]
    return sum(ns) / len(ns) / ctx["rounds"] / 1e6


def top_ops(devices: Sequence[dict], lo: int, hi: int, count: int = 10,
            stages: Optional[Dict[str, str]] = None) -> List[list]:
    """``[[name, seconds]]``: the innermost ops that took most device time
    within the window, averaged over the devices, named ``name (detail)``
    (the name-stack path where the trace gives one, else the shapes and
    op kind).  Given ``stages`` (:func:`op_stages` of the superstep), each
    name starts with where the op belongs, ``<stage>: ``: its stage for
    an op of the superstep that has one, else its program's name without
    ``jit_`` (``superstep``, ``evaluate``)."""
    total: Dict[str, int] = {}
    for dev in devices:
        at = _program_at(dev)
        for name, a, b, detail in leaves(dev["ops"]):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = f"{name} ({detail})" if detail else name
                if stages is not None:
                    key = f"{_where(name, at(a), stages)}: {key}"
                total[key] = total.get(key, 0) + b - a
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:count]
    return [[k, v / 1e9 / len(devices)] for k, v in ranked]


def _where(name: str, program: Optional[str], stages: Dict[str, str]
           ) -> str:
    """:func:`top_ops`' label of op ``name`` of ``program``."""
    if program is None:
        return "no program"
    if program.startswith(SUPERSTEP) and name in stages:
        return stage_of(stages[name])
    return program.split("(")[0].removeprefix("jit_")


def idle_gaps(device: dict, host: Sequence[Event], lo: int, hi: int,
              count: int = 10) -> List[list]:
    """``[[label, seconds]]``: the longest stretches of the window with no
    op on the device, each labelled by the innermost host event in flight
    at its middle (``idle`` where there is none)."""
    busy = merge([(a, b) for _, a, b, _ in device["ops"]], lo, hi)
    gaps, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:count]:
        mid = (a + b) // 2
        inside = [(e[2] - e[1], e[0]) for e in host if e[1] <= mid < e[2]]
        out.append([min(inside)[1] if inside else "idle", (b - a) / 1e9])
    return out


def host_span(host: Sequence[Event], name: str) -> Optional[Tuple[int, int]]:
    """``(start, end)`` of the host event called ``name``."""
    for e in host:
        if e[0] == name:
            return e[1], e[2]
    return None
