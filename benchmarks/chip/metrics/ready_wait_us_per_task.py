"""Scheduler queue time per task: the runtime's ``ready_wait_s`` counter (the
summed time tasks sat READY before a worker claimed them) over the tasks
launched, counted by their ``rt.launch`` spans."""
from program_trace import span_total


def read(ctx):
    n, _ = span_total(ctx, "rt.launch")
    if "ready_wait_s" not in ctx["counters"] or not n:
        return None
    return 1e6 * ctx["counters"]["ready_wait_s"] / n
