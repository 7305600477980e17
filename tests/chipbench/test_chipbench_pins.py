"""The tiny CNN cells' compared numbers, pinned exactly.

Each cell of ``test_chipbench_correct`` runs in a process of its own held
to one CPU core, so that XLA's CPU thread partitioning, which moves the
last bits of f32 sums, is the same on every machine.  The values are
those of the harness before the CNN became a family file: moving the
node model, its data and its counts out of the harness moved no
arithmetic of the program or of the reference."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent

PINNED = {
    "tiny-morph": {"loss0": 1.1581703610692615e-07,
                   "loss": 4.526764095003146e-08, "acc0": 0.0, "acc": 0.0,
                   "grad": 4.764043817578855e-08,
                   "change": 3.310896491442988e-08, "edges": 0},
    "tiny-epidemic": {"loss0": 3.180198486491385e-08,
                      "loss": 3.880628805190998e-08, "acc0": 0.0,
                      "acc": 0.0, "grad": 8.236026058127565e-08,
                      "change": 3.635920450714517e-08, "edges": 0},
}

RUN = """
import os
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import json, sys, time
from pathlib import Path
sys.path[:0] = sys.argv[3:]
from chipbench import harness
from test_chipbench_correct import SEED, write_cells

root = Path(sys.argv[1])
write_cells(root)
result = harness.run_cell(harness.load_cell(sys.argv[2], root), SEED, 0.05,
                          False, time.perf_counter())
print(json.dumps({k: v["value"] for k, v in result["compared"].items()}))
"""


@pytest.mark.parametrize("name", sorted(PINNED))
def test_tiny_cell_compares_exactly_as_pinned(tmp_path, name):
    proc = subprocess.run(
        [sys.executable, "-c", RUN, str(tmp_path / "checkout"), name,
         str(ROOT), str(ROOT / "src"), str(HERE)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == PINNED[name]
