"""Device milliseconds per evaluation in the evaluate program (every
node on the shared test set), mean over the cell's devices."""
from chipbench import trace


def read(ctx):
    devs = ctx["trace"]["devices"]
    lo, hi = ctx["window"]
    ns = [trace.module_ns(d, "jit_evaluate", lo, hi) for d in devs]
    if not devs or not any(ns):
        return None
    return sum(ns) / len(ns) / ctx["evals"] / 1e6
