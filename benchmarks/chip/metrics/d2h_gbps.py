"""Device-to-host rate of the transfer engine: the bytes the runtime counted
down (``bytes_d2h``) over the time in its ``rt.d2h`` spans, in GB/s. Under
``run_tasked`` each chunk comes down straight into its block of the
caller's output (``get(out=...)``); a plain ``get()`` downloads into a
pooled staging buffer."""
from program_trace import span_total


def read(ctx):
    _, seconds = span_total(ctx, "rt.d2h")
    nbytes = ctx["counters"].get("bytes_d2h", 0)
    return 1e-9 * nbytes / seconds if seconds > 0 and nbytes else None
