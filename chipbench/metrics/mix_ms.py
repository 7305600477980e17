"""Device milliseconds per round in the superstep's ops of stage ``mix``
(averaging each node's model with its in-neighbours', the collectives of
a sharded population among them), mean over the cell's devices."""
from chipbench import trace


def read(ctx):
    return trace.scope_ms_per_round(ctx, "mix")
