"""The chip benchmark's files: BENCHMARK.json and everything it names load
and keep to the benchmark's rules; the CNN family's FLOP and parameter
counts are pinned to hand counts; the command refuses to run without a
TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CNN, _ = harness.load_family("cnn")


def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") \
            and ".." not in p.split("/")
    assert BENCH["command"][1] == "chipbench/run.py"
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_lines_use_allowed_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], e[key]
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell)
    assert c["model"]["name"] in {x["name"] for x in BENCH["configs"]}
    assert c["traffic"]["strategy"] in ("morph", "epidemic")
    assert c["limits"] is not None
    assert set(c["limits"]) == set(compare.NAMES)
    assert any(v is not None for v in c["limits"].values())
    assert "round_ms" in [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in [m["name"] for m in c["end_to_end"]]
    assert c["per_layer"], "every cell reports a per-layer metric"
    assert c["chips"] in (1, 4)
    family = c["model"]["family"]
    for half in (family, f"{family}_reference"):
        assert (ROOT / "chipbench" / "families" / f"{half}.py").is_file()


def test_configs_are_their_files_and_keep_published_widths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    assert {c["name"] for c in BENCH["configs"]} \
        == {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        model = json.loads((ROOT / c["file"]).read_text())
        assert model["name"] == c["name"]
        assert model["source"] == c["source"]
        family, _ = harness.load_family(model["family"])
        # The sizes give the parameter count the file states.
        assert family.param_count(model) == model["params_per_node"]
        if c["reduced"]:
            # A cut names each changed key's published value and the
            # deployment the cut stands for.
            assert model["stands_for"]
            for key in c["reduced"]:
                assert model.get(key) != model["published"][key]
        else:
            # Uncut, the count is the one the source states.
            assert f"{model['params_per_node']:,} parameters" \
                in model["source_part"]
        if "forward_macs_per_sample" in model:
            assert sum(family.forward_macs(model).values()) \
                == model["forward_macs_per_sample"]


def test_per_layer_metrics_move_round_ms_in_cells_that_report_it():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] == "round_ms"
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "round_ms" in [e["name"] for e in
                                  harness.load_cell(cell)["end_to_end"]]
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


GN_LENET = [["conv", 32, 5], ["pool"], ["relu"], ["group_norm", 2],
            ["conv", 32, 5], ["pool"], ["relu"], ["group_norm", 2],
            ["conv", 64, 5], ["pool"], ["relu"], ["group_norm", 2],
            ["flatten"], ["dense", 10]]
FEMNIST_CNN = [["conv", 32, 5], ["relu"], ["pool"], ["conv", 64, 5],
               ["relu"], ["pool"], ["flatten"], ["dense", 512], ["relu"],
               ["dense", 62]]


@pytest.mark.parametrize("model,macs,params", [
    # conv 32x32x32x25x3 + 16x16x32x25x32 + 8x8x64x25x32, linear 1024x10
    ({"image_size": 32, "in_channels": 3, "layers": GN_LENET},
     2_457_600 + 6_553_600 + 3_276_800 + 10_240, 89_834),
    # conv 28x28x32x25 + 14x14x64x25x32, linear 3136x512 + 512x62
    ({"image_size": 28, "in_channels": 1, "layers": FEMNIST_CNN},
     627_200 + 10_035_200 + 1_605_632 + 31_744, 1_690_046),
])
def test_flops_pinned_to_hand_counts(model, macs, params):
    assert sum(CNN.forward_macs(model).values()) == macs
    assert CNN.param_count(model) == params
    fwd = CNN.forward_macs(model)
    assert CNN.train_flops_per_sample(model) == 2 * (3 * macs
                                                     - fwd["conv1"])
    traffic = {"nodes": 100, "batch": 8, "test": 512}
    assert CNN.eval_flops(model, traffic) == 2 * macs * 100 * 512
    d = params
    assert CNN.round_flops(model, traffic, 300, True) \
        == 800 * CNN.train_flops_per_sample(model) + 2 * 400 * d \
        + 2 * 100 * 100 * d
    assert CNN.round_flops(model, traffic, 300, False) \
        == CNN.round_flops(model, traffic, 300, True) - 2 * 100 * 100 * d


def test_peaks_are_keyed_by_device_kind():
    peak = harness.peak_lookup("TPU v5 lite")
    assert peak("bf16_flops_per_s") == 197e12
    assert peak("hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        harness.peak_lookup("cpu")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"],
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
