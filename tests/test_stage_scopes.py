"""The round's stage scopes land in the compiled programs.

Every round body names its work with ``jax.named_scope`` from the fixed
set ``repro.dlrt.compiled.STAGES``.  Here each engine mode is compiled
(never run) at a tiny size, and its post-optimization HLO is read back:

* every ``convolution`` and ``dot`` instruction's ``op_name`` lies under
  exactly one stage, so no contraction's device time falls outside the
  named stages;
* the stages present are those of the mode: Epidemic has no
  ``similarity``, Morph has ``similarity`` and ``topology``, ``net``,
  ``codec`` and ``draw`` appear only where configured.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InGraphEpidemicStrategy, InGraphMorphStrategy
from repro.data import (DeviceDataStream, dirichlet_partition,
                        make_image_classification, train_test_split)
from repro.data.pipeline import StackedBatcher
from repro.dlrt import (DecentralizedRunner, RunnerConfig, SweepSpec,
                        SweepSuperstep)
from repro.dlrt.compiled import STAGES
from repro.models.cnn import cnn_loss, cnn_params
from repro.models.tiny import mlp_loss, mlp_params
from repro.netsim import profiles
from repro.optim import sgd
from repro.sparse import SparseMorphStrategy

N, CLASSES, IMG = 5, 4, 8
CONTRACTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ "
                         r"(dot|convolution)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# A path component is a scope, or a scope wrapped by the transforms
# applied inside it: ``vmap(mix)``, ``transpose(jvp(local_step))``.
SCOPE = re.compile(r"(?:\w+\()*(\w+)\)*")


def _data():
    ds = make_image_classification(200, num_classes=CLASSES,
                                   image_size=IMG, seed=0)
    tr, te = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, N, 0.5,
                                np.random.default_rng(0))
    return tr, te, parts


def _cnn_init(key):
    return cnn_params(key, in_channels=3, num_classes=CLASSES,
                      image_size=IMG, width=4, dtype=jnp.float32)


def _engine_hlo(strategy, *, cnn=False, stream=True, **cfg_kw):
    tr, te, parts = _data()
    batcher = (DeviceDataStream(tr, parts, 4, seed=0) if stream
               else StackedBatcher(tr, parts, 4, seed=0))
    init, loss = (_cnn_init, cnn_loss) if cnn else (mlp_params, mlp_loss)
    runner = DecentralizedRunner(
        init_fn=init, loss_fn=loss, eval_fn=loss, optimizer=sgd(0.05),
        batcher=batcher,
        test_batch={"images": te.images[:16], "labels": te.labels[:16]},
        strategy=strategy,
        cfg=RunnerConfig(n_nodes=N, rounds=8, eval_every=4, compiled=True,
                         **cfg_kw))
    return runner._make_engine().compiled_hlo()


def _sweep_hlo():
    tr, te, parts = _data()
    spec = SweepSpec(seeds=[0, 1])
    sweep = SweepSuperstep(
        spec=spec, init_fn=mlp_params, loss_fn=mlp_loss, eval_fn=mlp_loss,
        optimizer=sgd(0.05),
        streams=[DeviceDataStream(tr, parts, 4, seed=s)
                 for s in spec.seeds],
        test_batch={"images": te.images[:16], "labels": te.labels[:16]},
        strategies=[InGraphMorphStrategy(n=N, k=2, view_size=4, seed=s,
                                         delta_r=2) for s in spec.seeds],
        cfg=RunnerConfig(n_nodes=N, rounds=8, eval_every=4))
    return sweep.compiled_hlo()


def _stages(op_name):
    names = set()
    for part in op_name.split("/"):
        m = SCOPE.fullmatch(part)
        names.add(m.group(1) if m else part)
    return names & set(STAGES)


def _morph():
    return InGraphMorphStrategy(n=N, k=2, view_size=4, seed=0)


def _epidemic():
    return InGraphEpidemicStrategy(n=N, k=2, seed=0)


MODES = {
    "dense-epidemic-cnn": (
        lambda: _engine_hlo(_epidemic(), cnn=True),
        {"draw", "local_step", "topology", "mix"}),
    "dense-morph": (
        lambda: _engine_hlo(_morph()),
        {"draw", "local_step", "similarity", "topology", "mix"}),
    "host-batches": (
        lambda: _engine_hlo(_morph(), stream=False),
        {"local_step", "similarity", "topology", "mix"}),
    "dense-network": (
        lambda: _engine_hlo(_morph(), net=profiles.dense_network(
            "wan", N, round_s=0.02)),
        {"draw", "local_step", "similarity", "topology", "net"}),
    "codec": (
        lambda: _engine_hlo(_morph(), compress="int8"),
        {"draw", "local_step", "codec", "similarity", "topology", "mix"}),
    "sparse": (
        lambda: _engine_hlo(SparseMorphStrategy(n=N, k=2, seed=0),
                            engine="sparse"),
        {"draw", "local_step", "topology", "mix"}),
    "sharded-one-device": (
        lambda: _engine_hlo(_morph(), mesh_devices=1),
        {"draw", "local_step", "similarity", "topology", "mix"}),
    "sweep": (
        _sweep_hlo,
        {"draw", "local_step", "similarity", "topology", "mix"}),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_contractions_lie_in_one_stage_and_stages_match_mode(mode):
    build, want = MODES[mode]
    hlo = build()
    present, stray, contractions = set(), [], 0
    for line in hlo.splitlines():
        m = OP_NAME.search(line)
        stages = _stages(m.group(1)) if m else set()
        present |= stages
        c = CONTRACTION.match(line)
        if c:
            contractions += 1
            if len(stages) != 1:
                stray.append(f"{c.group(1)}: "
                             f"{m.group(1) if m else '(no op_name)'}")
    assert contractions, "the program holds no dot or convolution"
    assert not stray, ("contractions outside exactly one stage:\n"
                       + "\n".join(stray))
    assert present == want
