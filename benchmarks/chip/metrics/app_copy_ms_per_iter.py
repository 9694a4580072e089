"""The application's own host copies per iteration: ``run_tasked``'s
``jacobi.split`` (the chunk copies and the objects made from them) and
``jacobi.assemble`` (the output put together from the chunks) spans, over
the iterations of the window."""
from program_trace import span_total


def read(ctx):
    spans = [span_total(ctx, n) for n in ("jacobi.split", "jacobi.assemble")]
    if not ctx["iterations"] or not any(n for n, _ in spans):
        return None
    return 1e3 * sum(s for _, s in spans) / ctx["iterations"]
