"""Host time per task submitted: the benchmark's spans around each
``Runtime.run`` call (dependency inference, scheduling, the lane hop), summed
over the window and divided by the tasks the runtime counted."""


def read(ctx):
    n, seconds = ctx["spans"].get("run", (0, 0.0))
    tasks = ctx["counters"].get("tasks", 0)
    if n == 0 or tasks == 0:
        return None
    return 1e6 * seconds / tasks
