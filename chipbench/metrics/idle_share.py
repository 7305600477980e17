"""Percent of the traced window in which no op ran on the device, mean
over the cell's devices."""
from chipbench import trace


def read(ctx):
    devs = ctx["trace"]["devices"]
    if not devs:
        return None
    lo, hi = ctx["window"]
    busy = sum(trace.busy_ns(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
