"""The compiled engine's host spans, read back from a profiler trace.

``CompiledSuperstep.run`` marks its segment loop with three
``jax.profiler.TraceAnnotation`` spans: ``dlrt.dispatch`` (launch a
superstep or an evaluation), ``dlrt.readback`` (fetch and decode its
results) and ``dlrt.progress`` (the caller's callback).  A tiny run of
three segments is traced on the CPU and the spans are read from the
host's Python line in the order the loop makes them.
"""
import glob
import os

import jax
import numpy as np

from repro.core import InGraphEpidemicStrategy
from repro.data import (DeviceDataStream, dirichlet_partition,
                        make_image_classification, train_test_split)
from repro.dlrt import DecentralizedRunner, RunnerConfig
from repro.models.tiny import mlp_loss, mlp_params
from repro.optim import sgd

N = 5
# One segment: a superstep and an evaluation, each launched and read
# back, then the callback.
SEGMENT = ["dlrt.dispatch", "dlrt.readback", "dlrt.dispatch",
           "dlrt.readback", "dlrt.progress"]


def _runner(rounds):
    ds = make_image_classification(200, num_classes=4, image_size=8, seed=0)
    tr, te = train_test_split(ds, 0.25)
    parts = dirichlet_partition(tr.labels, N, 0.5,
                                np.random.default_rng(0))
    return DecentralizedRunner(
        init_fn=mlp_params, loss_fn=mlp_loss, eval_fn=mlp_loss,
        optimizer=sgd(0.05), batcher=DeviceDataStream(tr, parts, 4, seed=0),
        test_batch={"images": te.images[:16], "labels": te.labels[:16]},
        strategy=InGraphEpidemicStrategy(n=N, k=2, seed=0),
        cfg=RunnerConfig(n_nodes=N, rounds=rounds, eval_every=4,
                         compiled=True))


def _dlrt_spans(log_dir):
    """``[(start_ns, end_ns, name)]`` of the ``dlrt.`` events in the
    trace, sorted by start, and the names of the lines holding them."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans, lines = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dlrt."):
                    spans.append((int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), e.name))
                    lines.add(line.name)
    return sorted(spans), lines


def test_each_segment_dispatches_reads_back_then_calls_back(tmp_path):
    # rounds 9 at eval_every 4: segments end after rounds 0, 4 and 8.
    runner = _runner(rounds=9)
    calls = []
    with jax.profiler.trace(str(tmp_path)):
        runner.run(lambda rec: calls.append(rec.rnd))
    assert calls == [0, 4, 8]
    spans, lines = _dlrt_spans(str(tmp_path))
    assert [name for _, _, name in spans] == SEGMENT * 3
    assert len(lines) == 1, lines
    # One thread, one after another: no span overlaps the next.
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
