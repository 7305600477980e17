"""The comparison that decides ``correct``.

Both sides are summarised by the same function of what they produced over
the first rounds (round 0 as its own superstep, then the first whole
segment): the params after round 0 and after the segment, the in-edges of every
round, and the two evaluations.  The numbers, each held to a limit of
its own (``chipbench/limits/<cell>.json``):

``loss0``, ``loss``
    relative gap of the mean evaluation loss after round 0, and after
    the segment;
``acc0``, ``acc``
    largest gap of one node's test accuracy after round 0, and after the
    segment;
``grad``
    round 0's update as each side's own mixing leaves it,
    ``(p1 - W0 p0) / lr`` with ``W0`` the uniform weights of that side's
    round-0 edges: per leaf, the gap between the two sides' norms (taken
    over all nodes) against the reference's norm of that leaf or of the
    median leaf, whichever is larger; the worst leaf;
``change``
    the same for the change ``p_end - p0`` over all compared rounds,
    leaving out leaves whose round-0 gradient in the reference is under
    a thousandth of the median leaf's (Adam or SGD moves those by
    round-off alone);
``edges``
    the number of (round, node) pairs whose in-edges differ, over all
    compared rounds: Morph's negotiations (selection, then matching) at
    every ``delta_r``-th round, Epidemic's draws at every round.  Morph's
    round 0 alone would say little: each node then knows only its two
    ring neighbours.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss0", "loss", "acc0", "acc", "grad", "change", "edges")
NEGLIGIBLE_GRAD = 1e-3     # of the median leaf's round-0 gradient norm


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_leaves(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: tree}


def _uniform(edges):
    n = edges.shape[0]
    w = edges.astype(np.float32) + np.eye(n, dtype=np.float32)
    return w / w.sum(axis=1, keepdims=True)


def _update_norms(p0, p1, edges0, lr):
    w = _uniform(edges0)
    out = {}
    for name, a in _leaves(p0).items():
        n = a.shape[0]
        mixed = w @ a.reshape(n, -1).astype(np.float32)
        b = _leaves(p1)[name].reshape(n, -1).astype(np.float32)
        out[name] = float(np.linalg.norm(b - mixed)) / lr
    return out


def _change_norms(p0, p_end):
    ends = _leaves(p_end)
    return {name: float(np.linalg.norm(
        ends[name].astype(np.float32) - a.astype(np.float32)))
        for name, a in _leaves(p0).items()}


def _worst_gap(prog, ref, keep=None):
    names = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
               for k in names)


def summarise(p0, p1, p_end, edges, evals, lr):
    """One side's record: ``p0`` the start (shared), ``p1`` after round 0,
    ``p_end`` after the compared rounds, ``edges`` every compared round's
    in-edges ``[rounds, n, n]``, ``evals`` [(mean loss, accuracy [n])]
    after round 0 and at the end."""
    edges = np.asarray(edges, bool)
    return {"update": _update_norms(p0, p1, edges[0], lr),
            "change": _change_norms(p0, p_end),
            "edges": edges,
            "evals": [(float(loss), np.asarray(acc, np.float64))
                      for loss, acc in evals]}


def numbers(prog, ref, grad0_norms):
    """The compared numbers of one run, program against reference."""
    g = _leaves(grad0_norms)
    median = float(np.median(list(g.values())))
    moved = {k for k, v in g.items() if v >= NEGLIGIBLE_GRAD * median}
    (l0, a0), (l1, a1) = prog["evals"]
    (r0, b0), (r1, b1) = ref["evals"]
    return {
        "loss0": abs(l0 - r0) / abs(r0),
        "loss": abs(l1 - r1) / abs(r1),
        "acc0": float(np.abs(a0 - b0).max()),
        "acc": float(np.abs(a1 - b1).max()),
        "grad": _worst_gap(prog["update"], ref["update"]),
        "change": _worst_gap(prog["change"], ref["change"], keep=moved),
        "edges": int((prog["edges"] != ref["edges"]).any(axis=2).sum()),
    }


def judge(nums, limits):
    """``(correct, lines)``: every number that has a limit within it, and
    one line per compared number with its limit.  A number whose limit is
    ``null`` is not compared (no reading separates sound runs from the
    control and the faults; ``PERF.md`` gives its readings)."""
    compared = [k for k in NAMES if limits.get(k) is not None]
    lines = [f"{k} {nums[k]!r} limit {limits[k]!r}" for k in compared]
    return all(nums[k] <= limits[k] for k in compared), lines
