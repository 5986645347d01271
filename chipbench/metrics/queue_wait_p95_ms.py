"""Nearest-rank 95th percentile of the ``queue.wait`` spans (submit to
the admission pop) that ended inside the window, in milliseconds.  The
cell is offered above capacity, so this reads seconds."""
from chipbench.harness import program_spans, traffic

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    waits = program_spans.resident("queue.wait", *program_spans.window(ctx),
                                   by="t_end")
    if not waits:
        return None
    return traffic.percentile(
        [1e3 * program_spans.duration(w) for w in waits], 0.95)
