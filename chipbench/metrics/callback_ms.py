"""Device-idle milliseconds per round while the host runs the caller's
progress callback at each evaluation (span ``dlrt.progress``; in the
benchmark, the harness's checks of the segment just run), mean over
the cell's devices."""
from chipbench import host_spans


def read(ctx):
    return host_spans.idle_ms_per_round(ctx, "dlrt.progress")
