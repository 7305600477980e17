"""Device-idle milliseconds per round while the host builds a superstep's
or an evaluation's inputs and launches it (span ``dlrt.dispatch``), mean
over the cell's devices."""
from chipbench import host_spans


def read(ctx):
    return host_spans.idle_ms_per_round(ctx, "dlrt.dispatch")
