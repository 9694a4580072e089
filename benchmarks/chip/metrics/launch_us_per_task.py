"""Worker time per task in ``Runtime._launch``: the ``rt.launch`` spans (the
wait for prefetched arguments, the coherence walk, the jit dispatch, output
binding, the lineage record) over their count."""
from program_trace import span_total


def read(ctx):
    n, seconds = span_total(ctx, "rt.launch")
    return 1e6 * seconds / n if n else None
