"""Device-idle milliseconds per round while the host fetches and decodes
results (span ``dlrt.readback``: the edge stack into ``edge_history``
and comm bytes, the evaluation's losses and accuracies into a record),
mean over the cell's devices."""
from chipbench import host_spans


def read(ctx):
    return host_spans.idle_ms_per_round(ctx, "dlrt.readback")
