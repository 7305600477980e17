"""The reference computed in node blocks.

Between steps the reference keeps the population on the host; the device
holds one block of ``b`` nodes for the local step and for evaluation,
and one leaf of the population for mixing and the Eq.-3 Gram.  Here:
``b`` follows the device's free memory; forcing small blocks on the tiny
cells gives the compared numbers of the whole population exactly; and a
planted family's reference never holds more than a block on the
device."""
import gc
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import reference  # noqa: E402

GB = 10**9
BLOCKS = (1, 3)


def _device(stats):
    return SimpleNamespace(memory_stats=lambda: stats)


@pytest.mark.parametrize("stats,n,node_bytes,want", [
    (None, 100, 359_336, 100),                       # the CPU: all
    ({"bytes_limit": 16 * GB, "bytes_in_use": GB}, 100, 359_336, 100),
    ({"bytes_limit": 500, "bytes_in_use": 0}, 100, 100, 1),
    ({"bytes_limit": 4800, "bytes_in_use": 1200}, 10, 100, 6),
    # four nodes of 2.14 GB on a 16 GB chip: one a block
    ({"bytes_limit": 16 * GB, "bytes_in_use": GB // 5}, 4, 2_140_243_968, 1),
], ids=["no-stats", "fits", "at-least-one", "most-that-fit", "large-node"])
def test_block_size_follows_free_device_memory(monkeypatch, stats, n,
                                               node_bytes, want):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_device(stats)])
    assert reference.block_size(n, node_bytes) == want


RUN = """
import os
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import json, sys
from pathlib import Path
sys.path[:0] = sys.argv[3:]
from chipbench import compare, harness
from test_chipbench_correct import SEED, write_cells

root = Path(sys.argv[1])
write_cells(root)
cell = harness.load_cell(sys.argv[2], root)
seeds = harness.sub_seeds(SEED)
train, parts, test = harness.build_data(cell, seeds)
win = harness.drive(harness.make_runner(cell, seeds, train, parts, test),
                    cell, None)
out = {}
for block in (None,) + tuple(BLOCKS):
    ref, grad0, p0 = harness.reference_summary(cell, seeds, train, parts,
                                               test, block=block)
    out[str(block)] = compare.numbers(harness.program_summary(cell, p0, win),
                                      ref, grad0)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def blocked(tmp_path_factory):
    """Per tiny cell, its compared numbers with the whole population in a
    block and with each forced block size, in a process held to one CPU
    core (as the pinned numbers are)."""
    cache = {}

    def numbers(name):
        if name not in cache:
            proc = subprocess.run(
                [sys.executable, "-c",
                 RUN.replace("tuple(BLOCKS)", repr(BLOCKS)),
                 str(tmp_path_factory.mktemp("checkout")), name, str(ROOT),
                 str(ROOT / "src"), str(HERE)],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=600)
            assert proc.returncode == 0, proc.stderr[-4000:]
            cache[name] = json.loads(proc.stdout.strip().splitlines()[-1])
        return cache[name]
    return numbers


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("name", ["tiny-epidemic", "tiny-morph"])
def test_forced_blocks_compare_as_the_whole_population(blocked, name, block):
    """Identical, not merely close: a block's local step is the
    population's vmapped step on fewer nodes, and mixing and the Gram
    contract the same leaves as before.  (The 16-node cells, so 1 and 3
    make blocks of one node and a short last block of 1.)"""
    got = blocked(name)
    assert got[str(block)] == got["None"]


class Tables:
    """A planted family of four equal tables ``[V, D]``: a token's logits
    are the first ``C`` columns of the sum of its rows.  Its parameters
    dwarf its data, so the device's live bytes show what the reference
    holds."""

    @staticmethod
    def init_node(key, model):
        import jax
        import jax.numpy as jnp
        keys = jax.random.split(key, 4)
        return {f"t{i}": jax.random.normal(
            k, (model["vocab"], model["width"]), jnp.float32)
            for i, k in enumerate(keys)}

    @staticmethod
    def loss_and_accuracy(p, batch, model):
        import jax
        import jax.numpy as jnp
        h = sum(p[f"t{i}"][batch["tokens"]] for i in range(4))
        z = h[..., :model["classes"]]
        y = batch["targets"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(z), y[..., None],
                                   axis=-1)[..., 0]
        return nll.mean(), (z.argmax(-1) == y).mean()

    @staticmethod
    def draw(train, rows, sizes, key, rnd, batch):
        import jax
        import jax.numpy as jnp
        k_round = jax.random.fold_in(key, rnd)

        def one(table, size, i):
            take = jax.random.randint(jax.random.fold_in(k_round, i),
                                      (batch,), 0, size)
            return jax.tree_util.tree_map(lambda x: x[table[take]], train)

        return jax.vmap(one)(rows, sizes, jnp.arange(rows.shape[0]))


@pytest.mark.parametrize("strategy", ["epidemic", "morph"])
def test_the_reference_holds_one_block_on_the_device(monkeypatch, strategy):
    """After every device call of a run in blocks of one node, the live
    device arrays beyond the data hold at most three node models (the
    step's new params and gradients, and its input) and at least two;
    mixing and the Gram, one leaf of four nodes in and out (two node
    models' bytes).  The whole population would be twelve."""
    import jax
    import jax.numpy as jnp

    model = {"vocab": 64, "width": 256, "classes": 8}
    node_bytes = 4 * 64 * 256 * 4
    n, seq = 4, 6
    rng = np.random.default_rng(0)

    def tokens(rows):
        return {"tokens": jnp.asarray(rng.integers(0, 64, (rows, seq)),
                                      jnp.int32),
                "targets": jnp.asarray(rng.integers(0, 8, (rows, seq)),
                                       jnp.int32)}

    train, test = tokens(40), tokens(8)
    parts = [np.arange(i, 40, n) for i in range(n)]
    live = []

    def base():
        return sum(x.nbytes for x in jax.live_arrays())

    def watched(fn, kind):
        def call(*args, **kw):
            out = fn(*args, **kw)
            live.append((kind, base() - start))
            return out
        return call

    for name, kind in [("local_step", "step"), ("evaluate", "step"),
                       ("_mix_leaf", "leaf"), ("_leaf_cos", "leaf")]:
        monkeypatch.setattr(reference, name,
                            watched(getattr(reference, name), kind))
    gc.collect()
    start = base()
    out = reference.run(
        seeds={"init": 1, "strategy": 2, "stream": 3}, family=Tables,
        model=model, traffic={"strategy": strategy, "nodes": n, "k": 2,
                              "delta_r": 2, "batch": 2, "lr": 0.1},
        train=train, parts=parts, test=test, rounds=3, block=1)
    assert out["block"] == 1
    kinds = {kind for kind, _ in live}
    assert kinds == {"step", "leaf"}
    slack = 64 * 1024          # keys, edges, [n, n] weights, counters
    steps = [b for kind, b in live if kind == "step"]
    assert max(steps) <= 3 * node_bytes + slack
    assert max(steps) >= 2 * node_bytes
    assert max(b for kind, b in live if kind == "leaf") \
        <= 2 * node_bytes + slack
    assert out["p_end"]["t0"].shape == (n, 64, 256)
