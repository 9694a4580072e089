"""Share of submitted tasks that ran as part of a replayed task graph."""


def read(ctx):
    tasks = ctx["counters"].get("tasks", 0)
    if not tasks:
        return None
    return ctx["counters"].get("replayed_tasks", 0) / tasks
