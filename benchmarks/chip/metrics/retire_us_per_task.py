"""Time per task in ``Runtime._finish``: the ``rt.retire`` spans (unpins,
dependency retirement, the pushes of tasks made ready, the notify) over
their count."""
from program_trace import span_total


def read(ctx):
    n, seconds = span_total(ctx, "rt.retire")
    return 1e6 * seconds / n if n else None
