"""Host time per task inside ``Runtime.submit``: the program's own
``rt.submit`` spans (argument pins, dependency inference, the scheduler
push) over their count. The inside twin of ``submit_us_per_task``."""
from program_trace import span_total


def read(ctx):
    n, seconds = span_total(ctx, "rt.submit")
    return 1e6 * seconds / n if n else None
