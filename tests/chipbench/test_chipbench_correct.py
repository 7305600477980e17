"""``correct`` at a size a test run holds, on the CPU.

A tiny cell is added as files alone (its configuration, traffic mix and
limits in a checkout of its own, beside a copy of the families), and the
whole run is driven past the chip check: a sound run comes out correct;
the bfloat16 control in the program's place, and each fault planted in
the timed path underneath, come out not correct; a cell on four chips
runs its population sharded over four devices and comes out correct.
The limits of the tiny cells are set from the CPU, where the program and
the reference both compute in f32 (they agree to about 1e-7)."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, faults, harness, reference  # noqa: E402

HERE = Path(__file__).resolve().parent

LIMITS = {"loss0": 1e-4, "loss": 1e-4, "acc0": 0.02, "acc": 0.02,
          "grad": 1e-3, "change": 1e-3, "edges": 0}
SEED = 2**32 + 5         # wider than 32 bits, as a run's seed may be


def write_cells(root: Path, chips: int = 1):
    """A checkout with two tiny cells on ``chips`` chips, Morph and
    Epidemic, added as files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "limits").mkdir(parents=True)
    shutil.copytree(ROOT / "chipbench" / "families",
                    root / "chipbench" / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tiny.json").write_text(json.dumps({
        "name": "tiny", "family": "cnn", "in_channels": 3,
        "num_classes": 10,
        "image_size": 8,
        "layers": [["conv", 4, 5], ["pool"], ["relu"], ["group_norm", 2],
                   ["conv", 8, 3], ["relu"], ["pool"], ["flatten"],
                   ["dense", 16], ["relu"], ["dense", 10]]}))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "tiny.json", "reduced": [], "why": "test"}]
    bench["workloads"] = []
    for strategy in ("morph", "epidemic"):
        name = f"tiny-{strategy}"
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps({"strategy": strategy, "nodes": 16, "k": 2,
                        "delta_r": 5, "alpha": 0.5, "batch": 4,
                        "lr": 0.05, "train": 1600, "test": 32,
                        "eval_every": 5, "eval_chunk": 16}))
        (root / "chipbench" / "limits" / f"{name}.json").write_text(
            json.dumps(LIMITS))
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": name, "chips": chips,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    write_cells(root)
    return root


def run(root, name, fault=None):
    cell = harness.load_cell(name, root)
    if fault is None:
        return harness.run_cell(cell, SEED, 0.05, False, time.perf_counter())
    with faults.planted(fault):
        return harness.run_cell(cell, SEED, 0.05, False, time.perf_counter())


@pytest.mark.parametrize("name", ["tiny-morph", "tiny-epidemic"])
def test_sound_run_is_correct(tiny, name):
    result = run(tiny, name)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-2:] == ["compared", "_lines"]
    assert set(result["metrics"]) == {"round_ms", "setup_s", "peak_hbm_gb"}
    assert result["device"]["count"] == 1


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "node_altered"])
def test_planted_fault_is_not_correct(tiny, fault):
    result = run(tiny, "tiny-morph", fault)
    assert not result["correct"], result["compared"]


def test_bfloat16_control_is_not_correct(tiny):
    cell = harness.load_cell("tiny-morph", tiny)
    seeds = harness.sub_seeds(SEED)
    import jax.numpy as jnp
    train, parts, test = harness.build_data(cell, seeds)
    ref, grad0, _ = harness.reference_summary(cell, seeds, train, parts,
                                              test)
    ctrl, _, _ = harness.reference_summary(cell, seeds, train, parts, test,
                                           dtype=jnp.bfloat16)
    ok, _ = compare.judge(compare.numbers(ctrl, ref, grad0), LIMITS)
    assert not ok


def test_reference_matching_equals_the_engines_fixpoint():
    """The reference's sequential deferred acceptance gives the engine's
    parallel matching, run to its fixpoint, on random markets (strict
    preferences)."""
    from repro.core.matching import match_jax
    rng = np.random.default_rng(0)
    for n, k in [(12, 2), (30, 3), (60, 3), (100, 3)]:
        for _ in range(5):
            recv = rng.normal(size=(n, n)).astype(np.float32)
            send = rng.normal(size=(n, n)).astype(np.float32)
            allowed = rng.random((n, n)) < 0.4
            want = np.asarray(match_jax(recv, send, allowed, k, k,
                                        rounds=n * n * k))
            got = reference.deferred_acceptance(recv, send, allowed, k, k)
            assert (want == got).all()


FOUR_CHIPS = """
import json, sys, time
from pathlib import Path
sys.path[:0] = sys.argv[2:]
import jax
from chipbench import harness
from test_chipbench_correct import SEED, write_cells

root = Path(sys.argv[1])
write_cells(root, chips=4)
runners = []
make_runner = harness.make_runner
harness.make_runner = lambda *a: runners.append(make_runner(*a)) or runners[-1]
out = {}
for name in ("tiny-morph", "tiny-epidemic"):
    result = harness.run_cell(harness.load_cell(name, root), SEED, 0.05,
                              False, time.perf_counter())
    leaves = jax.tree_util.tree_leaves(runners[-1].engine.params)
    out[name] = {"correct": result["correct"],
                 "compared": result["compared"],
                 "count": result["device"]["count"],
                 "devices": [len(x.sharding.device_set) for x in leaves],
                 "replicated": [x.sharding.is_fully_replicated
                                for x in leaves]}
print(json.dumps(out))
"""


def test_a_cell_on_four_chips_runs_sharded_and_correct(tmp_path):
    """A ``chips: 4`` cell builds its runner with ``mesh_devices=4``: on
    four forced CPU devices (in a process of its own, as XLA makes them
    at start-up) every node-model leaf is split over the four, and the
    run against the one-device reference comes out correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(tmp_path / "checkout"),
         str(ROOT), str(ROOT / "src"), str(HERE)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, run in out.items():
        assert run["correct"], (name, run["compared"])
        assert run["count"] == 4
        assert set(run["devices"]) == {4}
        assert not any(run["replicated"])


def test_the_timed_model_matches_the_reference_forward(tiny):
    """The program-built node model and the reference agree on logits
    (both f32 on the CPU) for the tiny cell's layer list."""
    import jax
    import jax.numpy as jnp

    cnn, cnn_reference = harness.load_family("cnn", tiny)
    model = harness.load_cell("tiny-morph", tiny)["model"]
    net = cnn_reference.arch(model)
    p = cnn_reference.init_node(jax.random.PRNGKey(3), model)
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 8, 8, 3), jnp.float32)
    np.testing.assert_allclose(cnn.forward(p, x, net),
                               cnn_reference.forward(p, x, net),
                               rtol=1e-5, atol=1e-5)
