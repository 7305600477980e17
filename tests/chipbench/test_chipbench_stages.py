"""Each device op's stage, handed to the readers.

The trace names a TPU op by its HLO instruction and carries no name
stack, so the harness maps instruction names to stages from the compiled
superstep's HLO text (``trace.op_stages``).  Here a synthetic HLO text
and a synthetic trace give known ``local_step_ms`` and ``mix_ms``; an op
that no stage claims counts in neither; the map of a real compiled
superstep (a tiny cell on the CPU) puts every convolution in
``local_step``; and a whole traced run hands the map to the readers and
labels the breakdown with it."""
import re
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace  # noqa: E402

STAGES = ("draw", "local_step", "codec", "similarity", "topology", "mix",
          "net")
MS = 1_000_000

# The shape of a compiled superstep: an entry computation holding the
# scan's ``while``, whose body holds the stages' ops, a fused computation
# and an inner loop of its own.
HLO = """\
HloModule jit_superstep, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  ROOT %multiply.9 = f32[8,4]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(superstep)/while/body/closed_call/mix/mul"}
}

%inner_body.2 (p.2: (s32[], u32[4])) -> (s32[], u32[4]) {
  %p.2 = (s32[], u32[4]{0}) parameter(0)
  ROOT %tuple.8 = (s32[], u32[4]{0}) tuple(%p.2), metadata={op_name="jit(superstep)/while/body/closed_call/topology/jit(_gumbel)/while/body/closed_call/add"}
}

%body.3 (p.3: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p.3 = (s32[], f32[8,4]{1,0}) parameter(0)
  %get-tuple-element.1 = f32[8,4]{1,0} get-tuple-element(%p.3), index=1
  %add.1 = s32[] add(%get-tuple-element.2, %constant.1), metadata={op_name="jit(superstep)/while/body/add"}
  %gather_fusion.2 = f32[8,2,4]{2,1,0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while/body/closed_call/draw/vmap(jit(_take))/gather"}
  %copy.3 = f32[8,2,4]{2,1,0:T(8,128)} copy(%gather_fusion.2)
  %convolution.4 = f32[8,4]{1,0} convolution(%copy.3, %get-tuple-element.1), window={size=3}, metadata={op_name="jit(superstep)/while/body/closed_call/local_step/transpose(jvp(forward))/conv_general_dilated" source_file="cnn.py" source_line=10}
  %fusion.5 = f32[8,4]{1,0} fusion(%convolution.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while/body/closed_call/vmap(mix)/dot_general"}
  %while.6 = (s32[], u32[4]{0}) while(%tuple.7), condition=%cond.4, body=%inner_body.2, metadata={op_name="jit(superstep)/while/body/closed_call/topology/jit(_gumbel)/while"}
  %copy.7 = f32[8,4]{1,0} copy(%fusion.5)
  %fusion.13 = f32[8,4]{1,0} fusion(%convolution.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while/body/closed_call/local_step/sgd/sub"}
  ROOT %tuple.9 = (s32[], f32[8,4]{1,0}) tuple(%add.1, %copy.7)
}

ENTRY %main.4 (a.1: f32[8,4]) -> (s32[], f32[8,4]) {
  %a.1 = f32[8,4]{1,0} parameter(0), metadata={op_name="carry[0]['w']"}
  %copy.10 = f32[8,4]{1,0:T(8,128)} copy(%a.1), metadata={op_name="jit(superstep)/copy"}
  ROOT %while.11 = (s32[], f32[8,4]{1,0}) while(%copy.10), condition=%cond.4, body=%body.3, metadata={op_name="jit(superstep)/while"}
}

FileNames
1 "cnn.py"
"""

FORWARD = "local_step/transpose(jvp(forward))/conv_general_dilated"
WANT = {"multiply.9": "mix/mul",
        "tuple.8": "topology/jit(_gumbel)/while/body/closed_call/add",
        "gather_fusion.2": "draw/vmap(jit(_take))/gather",
        "copy.3": FORWARD, "convolution.4": FORWARD,
        "fusion.5": "mix/dot_general",
        "while.6": "topology/jit(_gumbel)/while", "copy.7": "mix/dot_general",
        "fusion.13": "local_step/sgd/sub"}


def test_op_stages_of_a_synthetic_superstep():
    """Each instruction's scope path from the first stage after
    ``while/body/`` on, through ``closed_call`` and transform wrappers;
    the compiler's metadata-less ``copy.3`` takes its reader's path,
    ``copy.7`` (read by the untagged loop tuple) its operand's; the loop
    counter, the tuples, the parameters and the entry computation get
    none."""
    assert trace.op_stages(HLO, STAGES) == WANT
    assert {trace.stage_of(p) for p in WANT.values()} \
        == {"draw", "local_step", "mix", "topology"}


def _device(name, shift=0):
    # Window [0, 100 ms].  The superstep runs 0-60 ms: the scan's
    # ``while.11`` holds draw 0-5, local_step's forward 5-35 (the copy
    # 5-8 and the convolution 8-35), mix 35-45, topology 45-48, the loop
    # counter 48-50 (no stage), an instruction the map does not know
    # 50-55 and local_step's update 55-58.  Evaluate runs 70-90 ms and
    # holds a ``convolution.4`` of its own.
    s = shift
    ops = [("while.11", 0, 60, "while"), ("gather_fusion.2", 0, 5, ""),
           ("copy.3", 5, 8, ""), ("convolution.4", 8, 35, ""),
           ("fusion.5", 35, 42, ""), ("copy.7", 42, 45, ""),
           ("while.6", 45, 48, ""), ("add.1", 48, 50, ""),
           ("custom-call.12", 50, 55, ""), ("fusion.13", 55, 58, ""),
           ("convolution.4", 70, 90, "")]
    return {"name": name,
            "modules": [("jit_superstep(3)", 0 + s, 60 * MS + s, ""),
                        ("jit_evaluate(5)", 70 * MS + s, 90 * MS + s, "")],
            "ops": [(n, a * MS + s, b * MS + s, d) for n, a, b, d in ops]}


def _ctx(devices, stages=WANT):
    return harness.layer_context({"devices": devices, "host": []},
                                 (0, 100 * MS), len(devices), stages,
                                 rounds=10, evals=1, flops=0.0, peak=None)


def test_stage_times_and_readers_on_a_known_trace():
    dev = _device("/device:TPU:0")
    assert trace.stage_times(dev, WANT, 0, 100 * MS) == {
        "draw": 5 * MS, "local_step": 33 * MS, "mix": 10 * MS,
        "topology": 3 * MS, None: 7 * MS}
    ctx = _ctx([dev])
    assert harness.read_metric("local_step_ms", ctx) == pytest.approx(3.3)
    assert harness.read_metric("mix_ms", ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("scope,want", [
    ("local_step", 3.3), ("local_step/forward", 3.0),
    ("local_step/forward/conv_general_dilated", 3.0),
    ("local_step/sgd", 0.3), ("topology/_gumbel", 0.3),
    ("local_step/backward", None), ("forward", None)])
def test_a_scope_within_a_stage_is_read_from_the_same_map(scope, want):
    """What a later reader file of a scope inside a stage reads, such as
    a family's ``local_step/mla``: the ops whose path begins with the
    scope, transform wrappers unwrapped."""
    got = trace.scope_ms_per_round(_ctx([_device("/device:TPU:0")]), scope)
    assert got == (None if want is None else pytest.approx(want))


def test_an_op_no_stage_claims_counts_in_neither():
    # The loop counter (a stage-less path) and an instruction the map
    # does not hold fall under None; evaluate's ``convolution.4`` is not
    # the superstep's.  Dropping ``fusion.5`` from the map moves its 7 ms
    # out of mix and into no stage.
    stages = {k: v for k, v in WANT.items() if k != "fusion.5"}
    dev = _device("/device:TPU:0")
    times = trace.stage_times(dev, stages, 0, 100 * MS)
    assert times[None] == 14 * MS and times["mix"] == 3 * MS
    assert sum(times.values()) == 58 * MS
    ctx = _ctx([dev], stages)
    assert harness.read_metric("local_step_ms", ctx) == pytest.approx(3.3)
    assert harness.read_metric("mix_ms", ctx) == pytest.approx(0.3)


def test_readers_average_over_devices_and_clip_to_the_window():
    # Device 1 runs 50 ms later: its superstep's ops from 50 ms on fall in
    # the window, local_step's [55, 85] and mix's [85, 95] ms; its update
    # at [105, 108] ms falls out.
    devs = [_device("/device:TPU:0"), _device("/device:TPU:1", 50 * MS)]
    ctx = _ctx(devs)
    assert harness.read_metric("local_step_ms", ctx) == pytest.approx(
        (33 + 30) / 2 / 10)
    assert harness.read_metric("mix_ms", ctx) == pytest.approx(
        (10 + 10) / 2 / 10)


@pytest.mark.parametrize("metric", ["local_step_ms", "mix_ms"])
@pytest.mark.parametrize("case", ["no map", "stage not in the map",
                                  "no device"])
def test_readers_return_nothing_without_their_stage(metric, case):
    stage = metric[:-3]
    stages = {"no map": None,
              "stage not in the map": {k: v for k, v in WANT.items()
                                       if trace.stage_of(v) != stage},
              "no device": WANT}[case]
    devices = [] if case == "no device" else [_device("/device:TPU:0")]
    assert harness.read_metric(metric, _ctx(devices, stages)) is None


def test_layer_context_carries_the_stages():
    tr = {"devices": [_device("/device:TPU:0")], "host": []}
    ctx = harness.layer_context(tr, (0, 1), 1, WANT, rounds=1)
    assert ctx["stages"] == WANT
    assert harness.layer_context(tr, (0, 1), 1, rounds=1)["stages"] == {}


def test_breakdown_labels_carry_the_stage():
    top = dict(trace.top_ops([_device("/device:TPU:0")], 0, 100 * MS,
                             stages=WANT))
    assert top == {"local_step: convolution.4": 0.027,
                   "evaluate: convolution.4": 0.02,
                   "mix: fusion.5": 0.007, "superstep: custom-call.12": 0.005,
                   "draw: gather_fusion.2": 0.005, "local_step: copy.3": 0.003,
                   "mix: copy.7": 0.003, "topology: while.6": 0.003,
                   "local_step: fusion.13": 0.003, "superstep: add.1": 0.002}
    # Without a map the names are as they were.
    assert [n for n, _ in trace.top_ops([_device("/device:TPU:0")], 0,
                                        100 * MS)][0] == "convolution.4"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from test_chipbench_correct import write_cells
    root = tmp_path_factory.mktemp("checkout")
    write_cells(root)
    return root


def test_a_compiled_superstep_puts_every_convolution_in_local_step(tiny):
    """The real thing at a tiny size on the CPU: the stage map of the
    tiny Epidemic cell's compiled superstep holds every stage of its
    round, and every convolution instruction of the scan's body (the
    local step's forward and backward) is in ``local_step``."""
    from repro.dlrt.compiled import STAGES as PROGRAM_STAGES
    assert PROGRAM_STAGES == STAGES
    cell = harness.load_cell("tiny-epidemic", tiny)
    seeds = harness.sub_seeds(7)
    runner = harness.make_runner(cell, seeds,
                                 *harness.build_data(cell, seeds))
    hlo = runner._make_engine().compiled_hlo()
    stages = trace.op_stages(hlo, STAGES)
    assert {trace.stage_of(p) for p in stages.values()} \
        == {"draw", "local_step", "topology", "mix"}
    convs = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ convolution\(",
                       hlo, re.M)
    assert convs and {trace.stage_of(stages.get(c)) for c in convs} \
        == {"local_step"}


def test_a_traced_run_hands_the_map_to_readers_and_breakdown(tiny,
                                                             monkeypatch):
    """A whole traced run of the tiny cell on the CPU, whose profiler
    shows no device, handed a synthetic device trace instead: an op named
    as one of the superstep's convolutions is read as ``local_step_ms``
    and labelled ``local_step`` in the breakdown; an op named as no
    instruction is labelled by its program alone."""
    got = {}
    stages_of = harness.superstep_stages

    def keep(runner):
        got["stages"] = stages_of(runner)
        return got["stages"]

    def synthetic(_):
        conv = next(k for k, v in sorted(got["stages"].items())
                    if trace.stage_of(v) == "local_step" and "conv" in k)
        return {"devices": [{
            "name": "/device:TPU:0",
            "modules": [("jit_superstep(1)", 0, 50 * MS, "")],
            "ops": [(conv, 0, 30 * MS, ""), ("no-such-op", 30 * MS,
                                              40 * MS, "")]}],
            "host": [("chipbench.traced", 0, 100 * MS, "python")]}

    monkeypatch.setattr(harness, "superstep_stages", keep)
    monkeypatch.setattr(trace, "load", synthetic)
    monkeypatch.setattr(harness, "peak_lookup",
                        lambda kind: lambda key: 197e12)
    cell = harness.load_cell("tiny-epidemic", tiny)
    result = harness.run_cell(cell, 11, 0.05, True, time.perf_counter())
    assert result["correct"], result["compared"]
    rounds = cell["traffic"]["eval_every"] * harness.TRACE_SEGMENTS
    assert result["metrics"]["local_step_ms"]["value"] == pytest.approx(
        30 / rounds)
    assert result["metrics"]["mix_ms"]["value"] == 0.0
    labels = [name for name, _ in result["breakdown"]["device_ops"]]
    assert labels[0].startswith("local_step: conv")
    assert labels[1] == "superstep: no-such-op"
