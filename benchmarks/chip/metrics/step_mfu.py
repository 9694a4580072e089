"""The whole iteration's share of the chips' peak: the least time the chips
could take for one iteration over the traced window's time per iteration.
For the stencil the least time is the HBM bound (each point read and written
once, float32), which lies far above the operation bound; the larger of the
two is taken."""


def read(ctx):
    work, t, peaks = ctx["work"], ctx["trace"], ctx["peaks"]
    if not t or not t["window_s"] or not ctx["iterations"]:
        return None
    least = max(work["step_bytes"] / peaks["hbm_bytes_per_s"],
                work["step_ops"] / peaks["bf16_flops_per_s"]) / ctx["chips"]
    return 100.0 * least / (t["window_s"] / ctx["iterations"])
