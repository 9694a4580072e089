"""The update tasks' share of their HBM roofline: the least time their work
takes at the chip's peak bandwidth over the device time of the programs the
update task compiles to, found in the trace by the task's module name (so a
Pallas stencil inside the task is counted the same way). The work is counted
from the chunk shapes: read the chunk and its six faces, write the chunk."""


def read(ctx):
    work, t = ctx["work"], ctx["trace"]
    if not t or not work["update_bytes"]:
        return None
    n, seconds = t["modules"].get(work["update_module"], (0, 0.0))
    expected = work["iterations"] * len(work["update_bytes"])
    if n != expected or seconds <= 0:
        return None
    per_update = sum(work["update_bytes"]) / len(work["update_bytes"])
    least = n * per_update / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
