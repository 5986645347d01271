"""Share of the traced window in which the device was idle while the
engine's thread was in none of its model or cache calls:
``engine.iteration``'s own time (retire, the queue pop, bookkeeping),
``engine.emit``, and the time outside any span (the loop around
``run_iteration``)."""
from chipbench.harness import program_spans

LAYER = "device"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def read(ctx):
    return program_spans.idle_pct(ctx, "engine_host")
