"""The runner-layer readers (``readback_ms``, ``dispatch_ms``,
``callback_ms``) on a synthetic trace whose idle time under each of the
program's host spans is known."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, host_spans  # noqa: E402

MS = 1_000_000
READERS = ("readback_ms", "dispatch_ms", "callback_ms")


def _device(name, shift=0):
    # Busy 0-40, 50-70, 75-95 ms; idle in the window [0, 100 ms]:
    # 40-50, 70-75, 95-100.
    s = shift
    return {"name": name, "modules": [],
            "ops": [("while.1", 0 + s, 40 * MS + s, ""),
                    ("fusion.2", 50 * MS + s, 70 * MS + s, ""),
                    ("convolution.4", 75 * MS + s, 95 * MS + s, "")]}


HOST = [("chipbench.traced", 0, 100 * MS, "python"),
        # A span that starts before the window: its idle there is cut.
        ("dlrt.progress", -20 * MS, 1 * MS, "python"),
        # Waits on the running superstep (busy to 40), then 5 ms idle.
        ("dlrt.readback", 20 * MS, 45 * MS, "python"),
        ("PjitFunction(superstep)", 46 * MS, 47 * MS, "python"),
        # 5 ms idle, then 2 ms while the device is busy again.
        ("dlrt.dispatch", 45 * MS, 52 * MS, "python"),
        # Entirely while the device is busy: counts nothing.
        ("dlrt.readback", 55 * MS, 65 * MS, "python"),
        ("dlrt.readback", 70 * MS, 72 * MS, "python"),
        ("dlrt.progress", 72 * MS, 74 * MS, "python"),
        ("dlrt.dispatch", 74 * MS, 76 * MS, "python"),
        # Runs past the window's end: only 97-100 counts.
        ("dlrt.progress", 97 * MS, 110 * MS, "python")]


def _ctx(devices, host=HOST, rounds=50):
    return harness.layer_context({"devices": devices, "host": host},
                                 (0, 100 * MS), len(devices) or 1,
                                 rounds=rounds, evals=2, flops=0.0,
                                 peak=None)


@pytest.mark.parametrize("metric,want_ms", [
    ("readback_ms", 5 + 2), ("dispatch_ms", 5 + 1),
    ("callback_ms", 2 + 3)])
def test_idle_under_each_span_per_round(metric, want_ms):
    ctx = _ctx([_device("/device:TPU:0")])
    assert harness.read_metric(metric, ctx) == pytest.approx(want_ms / 50)


def test_the_three_spans_cover_the_idle_they_sit_on():
    # Of the window's 20 idle ms only 95-97 lies under no span.
    ctx = _ctx([_device("/device:TPU:0")])
    total = sum(harness.read_metric(m, ctx) for m in READERS) * 50
    assert total == pytest.approx(18.0)


def test_a_span_over_busy_device_counts_nothing():
    host = [("dlrt.readback", 0, 40 * MS, "python"),
            ("dlrt.readback", 52 * MS, 68 * MS, "python")]
    ctx = _ctx([_device("/device:TPU:0")], host=host)
    assert harness.read_metric("readback_ms", ctx) == 0.0
    assert harness.read_metric("dispatch_ms", ctx) == 0.0


def test_spans_are_clipped_to_the_window():
    ctx = _ctx([_device("/device:TPU:0")])
    # The window ends at 98 ms: of the last progress span only 97-98.
    ctx["window"] = (0, 98 * MS)
    assert harness.read_metric("callback_ms", ctx) == pytest.approx(
        (2 + 1) / 50)
    # The window starts at 42 ms: of the first readback only 42-45.
    ctx["window"] = (42 * MS, 100 * MS)
    assert harness.read_metric("readback_ms", ctx) == pytest.approx(
        (3 + 2) / 50)


def test_mean_over_two_devices():
    # Device 1 runs 10 ms later: busy 10-50, 60-80, 85-100 in the
    # window, idle 0-10, 50-60, 80-85.  Under readback it idles 55-60,
    # under dispatch 50-52, under progress 0-1.
    devs = [_device("/device:TPU:0"), _device("/device:TPU:1", 10 * MS)]
    ctx = _ctx(devs)
    assert harness.read_metric("readback_ms", ctx) == pytest.approx(
        (7 + 5) / 2 / 50)
    assert harness.read_metric("dispatch_ms", ctx) == pytest.approx(
        (6 + 2) / 2 / 50)
    assert harness.read_metric("callback_ms", ctx) == pytest.approx(
        (5 + 1) / 2 / 50)


@pytest.mark.parametrize("metric", READERS)
def test_nothing_without_a_device(metric):
    assert harness.read_metric(metric, _ctx([])) is None


@pytest.mark.parametrize("metric", READERS)
def test_nothing_without_the_programs_spans(metric):
    # The trace of a program without the spans (the harness's own spans
    # and Python frames only): the metric is left out, not read as 0.
    host = [e for e in HOST if not e[0].startswith("dlrt.")]
    assert harness.read_metric(metric, _ctx([_device("/device:TPU:0")],
                                            host=host)) is None


def test_interval_helpers():
    assert host_spans.idle([(2, 4), (6, 7)], 0, 10) \
        == [(0, 2), (4, 6), (7, 10)]
    assert host_spans.idle([], 3, 5) == [(3, 5)]
    assert host_spans.idle([(0, 10)], 0, 10) == []
    assert host_spans.overlap_ns([(0, 5), (8, 12)], [(3, 9), (11, 20)]) \
        == 2 + 1 + 1
    assert host_spans.overlap_ns([], [(0, 1)]) == 0
