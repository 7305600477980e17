"""Model FLOP utilization of the traced segments, in percent: the FLOPs
the rounds and evaluations require (``chipbench/flops.py``) over the
traced window times the chips times the chip's bf16 peak."""


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    lo, hi = ctx["window"]
    peak = ctx["peak"]("bf16_flops_per_s")
    return 100.0 * ctx["flops"] / ((hi - lo) / 1e9 * ctx["chips"] * peak)
