"""Device milliseconds per round in the superstep's ops of stage
``local_step`` (every node's SGD step: forward, backward and update,
vmapped over the population), mean over the cell's devices."""
from chipbench import trace


def read(ctx):
    return trace.scope_ms_per_round(ctx, "local_step")
