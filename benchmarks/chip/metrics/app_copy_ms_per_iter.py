"""The application's own host copies per iteration: ``run_tasked``'s
``jacobi.split`` spans (the chunk copies and the objects made from them),
over the iterations of the window. A program whose ``run_tasked`` still
copies its output together from the chunks has ``jacobi.assemble`` spans
too, and they are added; ``run_tasked`` as it stands downloads each
chunk into the output."""
from program_trace import span_total


def read(ctx):
    spans = [span_total(ctx, n) for n in ("jacobi.split", "jacobi.assemble")]
    if not ctx["iterations"] or not any(n for n, _ in spans):
        return None
    return 1e3 * sum(s for _, s in spans) / ctx["iterations"]
