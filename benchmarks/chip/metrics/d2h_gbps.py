"""Device-to-host rate of the transfer engine: the bytes the runtime counted
down (``bytes_d2h``) over the time in its ``rt.d2h`` spans (the download
into pooled staging buffers), in GB/s."""
from program_trace import span_total


def read(ctx):
    _, seconds = span_total(ctx, "rt.d2h")
    nbytes = ctx["counters"].get("bytes_d2h", 0)
    return 1e-9 * nbytes / seconds if seconds > 0 and nbytes else None
