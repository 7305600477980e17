"""The trace reducer and the per-layer readers on a synthetic trace whose
busy, idle and per-program times are known."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace  # noqa: E402

MS = 1_000_000


def _device(name, shift=0):
    # A 100 ms window [0, 100 ms]: the superstep program runs 0-40 ms and
    # 50-70 ms, evaluate 75-95 ms.  Ops: a scan ``while`` 0-40 holding a
    # fusion 0-25 and an all-gather 25-40, a fusion 50-70, a conv 75-95.
    # Idle: 40-50, 70-75, 95-100 = 20 ms.
    s = shift
    return {"name": name,
            "modules": [("jit_superstep(7)", 0 + s, 40 * MS + s, ""),
                        ("jit_superstep(7)", 50 * MS + s, 70 * MS + s, ""),
                        ("jit_evaluate(9)", 75 * MS + s, 95 * MS + s, "")],
            "ops": [("while.1", 0 + s, 40 * MS + s, "while"),
                    ("fusion.1", 0 + s, 25 * MS + s, "jit(superstep)/conv"),
                    ("all-gather.3", 25 * MS + s, 40 * MS + s, ""),
                    ("fusion.2", 50 * MS + s, 70 * MS + s, ""),
                    ("convolution.4", 75 * MS + s, 95 * MS + s, "")]}


HOST = [("chipbench.traced", 0, 100 * MS, "python"),
        ("chipbench.segment 3", 0, 72 * MS, "python"),
        ("PjitFunction(evaluate)", 71 * MS, 76 * MS, "python"),
        ("chipbench.segment 4", 72 * MS, 100 * MS, "python")]
TRACE = {"devices": [_device("/device:TPU:0")], "host": HOST}


def test_merge_and_busy_idle():
    assert trace.merge([(5, 9), (0, 3), (2, 4), (20, 30)], 1, 25) \
        == [(1, 4), (5, 9), (20, 25)]
    dev = TRACE["devices"][0]
    assert trace.busy_ns(dev, 0, 100 * MS) == 80 * MS
    assert trace.busy_ns(dev, 10 * MS, 60 * MS) == 40 * MS


def test_per_program_time():
    dev = TRACE["devices"][0]
    assert trace.module_ns(dev, "jit_superstep", 0, 100 * MS) == 60 * MS
    assert trace.module_ns(dev, "jit_evaluate", 0, 100 * MS) == 20 * MS
    assert trace.module_ns(dev, "jit_superstep", 35 * MS, 55 * MS) \
        == 10 * MS


def test_leaves_drop_ops_that_hold_others():
    names = [e[0] for e in trace.leaves(TRACE["devices"][0]["ops"])]
    assert names == ["fusion.1", "all-gather.3", "fusion.2",
                     "convolution.4"]


def test_gaps_labelled_by_host_span_and_top_ops():
    gaps = trace.idle_gaps(TRACE["devices"][0], HOST, 0, 100 * MS)
    assert gaps[0] == ["chipbench.segment 3", 0.01]
    assert sorted(g[1] for g in gaps) == [0.005, 0.005, 0.01]
    assert ["PjitFunction(evaluate)", 0.005] in gaps
    top = trace.top_ops(TRACE["devices"], 0, 100 * MS)
    assert top[0] == ["fusion.1 (jit(superstep)/conv)", 0.025]
    assert len(top) == 4
    assert trace.host_span(HOST, "chipbench.traced") == (0, 100 * MS)


def _ctx(tr):
    return {"trace": tr, "window": (0, 100 * MS), "rounds": 50, "evals": 2,
            "flops": 197e12 * 0.1 * 0.05, "chips": 1,
            "peak": harness.peak_lookup("TPU v5 lite")}


@pytest.mark.parametrize("metric,want", [
    ("superstep_ms", 60 / 50), ("eval_ms", 20 / 2), ("idle_share", 20.0),
    ("mfu", 5.0)])
def test_readers_on_a_known_trace(metric, want):
    assert harness.read_metric(metric, _ctx(TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["superstep_ms", "eval_ms", "idle_share",
                                    "mfu"])
def test_readers_return_nothing_without_a_device(metric):
    assert harness.read_metric(metric, _ctx({"devices": [],
                                             "host": HOST})) is None


def test_readers_average_over_devices():
    tr = {"devices": [_device("/device:TPU:0"),
                      _device("/device:TPU:1", shift=10 * MS)],
          "host": HOST}
    # device 1 is shifted by 10 ms: its last 10 ms of evaluate fall out of
    # the window, and its idle covers 0-10, 50-60, 80-85.
    assert harness.read_metric("eval_ms", _ctx(tr)) == pytest.approx(
        (20 + 15) / 2 / 2)
    assert harness.read_metric("idle_share", _ctx(tr)) == pytest.approx(
        (20 + 25) / 2)


@pytest.mark.parametrize("metric,want", [
    ("superstep_ms", 60 / 50), ("eval_ms", 20 / 2), ("idle_share", 20.0)])
def test_readers_see_only_the_cells_chips(metric, want):
    # A machine that shows two devices to a one-chip cell: the second
    # device, idle but for 10 ms of evaluate, is not the cell's.
    idle = {"name": "/device:TPU:1", "ops": [("convolution.9", 0, 10 * MS,
                                               "")],
            "modules": [("jit_evaluate(9)", 0, 10 * MS, "")]}
    tr = {"devices": [_device("/device:TPU:0"), idle], "host": HOST}
    ctx = harness.layer_context(tr, (0, 100 * MS), 1, rounds=50, evals=2,
                                flops=0.0, peak=None)
    assert [d["name"] for d in ctx["trace"]["devices"]] == ["/device:TPU:0"]
    assert harness.read_metric(metric, ctx) == pytest.approx(want)
