"""Node-model families are files found by name.

A toy token family (next-token prediction, both halves in plain
``jax.numpy``) is planted in a checkout of its own with its
configuration, traffic mix, limits and cell, and nothing of the harness
changes: a sound run comes out correct, and the local step on half the
batch comes out not correct.  Every family's reference half imports
nothing of the program."""
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import faults, harness  # noqa: E402

SEED = 2**33 + 17
LIMITS = {"loss0": 1e-4, "loss": 1e-4, "acc0": 0.02, "acc": 0.02,
          "grad": 1e-3, "change": 1e-3, "edges": 0}

TOY_REFERENCE = '''
"""The toy token family's reference half: next-token logits from the
current token's embedding plus the mean embedding of the sequence so far,
through a linear head, at HIGHEST."""
import math

import jax
import jax.numpy as jnp

from chipbench.reference import leaf_dtype, precision


def init_node(key, model):
    v, d = model["vocab"], model["width"]
    ke, kw = jax.random.split(key)
    return {"embed": jax.random.normal(ke, (v, d), jnp.float32),
            "head": {"w": jax.random.normal(kw, (d, v), jnp.float32)
                     / math.sqrt(d),
                     "b": jnp.zeros((v,), jnp.float32)}}


def loss_and_accuracy(p, batch, model):
    e = p["embed"][batch["tokens"]]
    t = jnp.arange(1, e.shape[1] + 1, dtype=e.dtype)
    h = e + jnp.cumsum(e, axis=1) / t[:, None]
    z = jnp.einsum("btd,dv->btv", h, p["head"]["w"],
                   precision=precision(leaf_dtype(p))) + p["head"]["b"]
    y = batch["targets"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z), y[..., None],
                               axis=-1)[..., 0]
    return nll.mean(), (z.argmax(-1) == y).mean()


def draw(train, rows, sizes, key, rnd, batch):
    k_round = jax.random.fold_in(key, rnd)

    def one(table, size, i):
        take = jax.random.randint(jax.random.fold_in(k_round, i),
                                  (batch,), 0, size)
        return jax.tree_util.tree_map(lambda x: x[table[take]], train)

    return jax.vmap(one)(rows, sizes, jnp.arange(rows.shape[0]))
'''

TOY = '''
"""The toy token family: sequences of tokens drawn from a topic's
unigram, split over the nodes by topic."""
import functools
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, harness

reference = harness.load_module(
    Path(__file__).with_name("toytok_reference.py"))


def build(seed, model, traffic):
    v, seq, topics = model["vocab"], model["seq"], model["topics"]
    kp, ktr, kte = jax.random.split(jax.random.PRNGKey(seed), 3)
    logp = jax.nn.log_softmax(3.0 * jax.random.normal(kp, (topics, v)))

    def make(key, n):
        kl, kt = jax.random.split(key)
        topic = jax.random.permutation(kl, jnp.arange(n) % topics)
        toks = jax.random.categorical(kt, logp[topic][:, None, :],
                                      shape=(n, seq + 1)).astype(jnp.int32)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, topic

    train, topic = make(ktr, traffic["train"])
    test, _ = make(kte, traffic["test"])
    parts = data.dirichlet_partition(np.asarray(topic), traffic["nodes"],
                                     traffic["alpha"],
                                     np.random.default_rng(seed))
    return train, parts, test


def param_count(model):
    return 2 * model["vocab"] * model["width"] + model["vocab"]


def _forward(model):
    return 2 * model["seq"] * model["width"] * model["vocab"]


def eval_flops(model, traffic):
    return _forward(model) * traffic["nodes"] * traffic["test"]


def round_flops(model, traffic, edges, negotiates):
    n, d = traffic["nodes"], param_count(model)
    return (n * traffic["batch"] * 2 * _forward(model)
            + 2 * (edges + n) * d + (2 * n * n * d if negotiates else 0))


def node(model):
    def loss(p, batch):
        e = jax.nn.one_hot(batch["tokens"], model["vocab"]) @ p["embed"]
        h = e + jnp.cumsum(e, axis=1) / jnp.arange(1, e.shape[1] + 1)[:, None]
        z = h @ p["head"]["w"] + p["head"]["b"]
        y = batch["targets"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(z), y[..., None],
                                   axis=-1)[..., 0]
        mean = nll.mean()
        return mean, {"loss": mean, "accuracy": (z.argmax(-1) == y).mean()}

    init = jax.jit(functools.partial(reference.init_node, model=model))
    return init, loss, loss


def batcher(train, parts, traffic, stream_seed):
    from repro.data import DeviceDataStream

    stream = DeviceDataStream(
        SimpleNamespace(images=train["tokens"], labels=train["targets"]),
        parts, traffic["batch"], seed=stream_seed)
    stream.data = dict(train)
    return stream
'''


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A checkout holding the toy family, its configuration, a traffic
    mix, limits and one cell, and nothing else of the families."""
    root = tmp_path_factory.mktemp("toy-checkout")
    chip = root / "chipbench"
    for sub in ("families", "traffic", "limits"):
        (chip / sub).mkdir(parents=True)
    (chip / "families" / "toytok.py").write_text(textwrap.dedent(TOY))
    (chip / "families" / "toytok_reference.py").write_text(
        textwrap.dedent(TOY_REFERENCE))
    (root / "toytok.json").write_text(json.dumps({
        "name": "toytok", "family": "toytok", "vocab": 32, "width": 16,
        "seq": 8, "topics": 4}))
    (chip / "traffic" / "toy-epidemic.json").write_text(json.dumps({
        "strategy": "epidemic", "nodes": 8, "k": 2, "delta_r": 5,
        "alpha": 0.5, "batch": 4, "lr": 0.1, "train": 640, "test": 32,
        "eval_every": 5, "eval_chunk": 16}))
    (chip / "limits" / "toy-epidemic.json").write_text(json.dumps(LIMITS))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "toytok", "source": "test",
                         "file": "toytok.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "toy-epidemic", "config": "toytok",
                           "traffic": "toy-epidemic", "chips": 1,
                           "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_a_planted_family_runs_and_is_judged(toy, fault):
    cell = harness.load_cell("toy-epidemic", toy)
    if fault is None:
        result = harness.run_cell(cell, SEED, 0.05, False,
                                  time.perf_counter())
        assert result["correct"], result["compared"]
        assert result["attempted"] >= 1 and result["failed"] == 0
        assert set(result["metrics"]) == {"round_ms", "setup_s",
                                          "peak_hbm_gb"}
        return
    with faults.planted(fault):
        result = harness.run_cell(cell, SEED, 0.05, False,
                                  time.perf_counter())
    assert not result["correct"], result["compared"]


IMPORTS = """
import json, sys
sys.path[:0] = sys.argv[2:]
from chipbench import harness
harness.load_module(sys.argv[1])
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""


@pytest.mark.parametrize("path", sorted(
    (ROOT / "chipbench" / "families").glob("*_reference.py")),
    ids=lambda p: p.stem)
def test_reference_half_imports_nothing_of_the_program(path):
    proc = subprocess.run(
        [sys.executable, "-c", IMPORTS, str(path), str(ROOT),
         str(ROOT / "src")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == []
