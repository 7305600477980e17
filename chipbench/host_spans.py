"""Device idle time under the program's host spans.

The engine's segment loop marks what the host is doing with three
profiler spans on the device trace's clock (``repro.dlrt.compiled``):
``dlrt.dispatch`` (launching a superstep or an evaluation),
``dlrt.readback`` (fetching and decoding its results) and
``dlrt.progress`` (the caller's callback).  The runner-layer metrics
read, per span name, the part of the traced window in which the host was
inside such a span and no op ran on the device: the idle time that span
holds the chip for.  A span during which the device is busy, such as a
readback waiting on the running superstep, counts nothing.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from chipbench import trace


def idle(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The gaps of ``[lo, hi]`` between the sorted disjoint ``busy``
    intervals."""
    out, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def overlap_ns(xs: Sequence[Tuple[int, int]],
               ys: Sequence[Tuple[int, int]]) -> int:
    """Nanoseconds common to two lists of sorted disjoint intervals."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_round(ctx: dict, span: str) -> Optional[float]:
    """Device-idle milliseconds per round while the host is inside spans
    called ``span``, mean over the cell's devices; None where the trace
    has no device or no ``dlrt.`` span at all (a program without the
    spans)."""
    devs, host = ctx["trace"]["devices"], ctx["trace"]["host"]
    if not devs or not any(e[0].startswith("dlrt.") for e in host):
        return None
    lo, hi = ctx["window"]
    inside = trace.merge([(a, b) for name, a, b, _ in host if name == span],
                         lo, hi)
    ns = [overlap_ns(idle(trace.merge([(a, b) for _, a, b, _ in d["ops"]],
                                      lo, hi), lo, hi), inside)
          for d in devs]
    return sum(ns) / len(ns) / ctx["rounds"] / 1e6
