"""Host-to-device rate of the transfer engine: the bytes the runtime counted
up (``bytes_h2d``) over the time in its ``rt.h2d`` spans, in GB/s."""
from program_trace import span_total


def read(ctx):
    _, seconds = span_total(ctx, "rt.h2d")
    nbytes = ctx["counters"].get("bytes_h2d", 0)
    return 1e-9 * nbytes / seconds if seconds > 0 and nbytes else None
