"""Device milliseconds per round in the superstep program (the engine's
fused K-round ``lax.scan``), mean over the cell's devices."""
from chipbench import trace


def read(ctx):
    devs = ctx["trace"]["devices"]
    lo, hi = ctx["window"]
    ns = [trace.module_ns(d, "jit_superstep", lo, hi) for d in devs]
    if not devs or not any(ns):
        return None
    return sum(ns) / len(ns) / ctx["rounds"] / 1e6
