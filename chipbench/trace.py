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
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int, str]


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


def top_ops(devices: Sequence[dict], lo: int, hi: int, count: int = 10
            ) -> List[list]:
    """``[[name, seconds]]``: the innermost ops that took most device time
    within the window, averaged over the devices, named ``name (detail)``
    (the name-stack path where the trace gives one, else the shapes and
    op kind)."""
    total: Dict[str, int] = {}
    for dev in devices:
        for name, a, b, detail in leaves(dev["ops"]):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = f"{name} ({detail})" if detail else name
                total[key] = total.get(key, 0) + b - a
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:count]
    return [[k, v / 1e9 / len(devices)] for k, v in ranked]


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
