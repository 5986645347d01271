"""Mean milliseconds an iteration spends in the engine's own host code:
``engine.iteration``'s self time (retire, the queue pop, slot-table and
sampling-lane bookkeeping, counters) plus its ``engine.emit``, over the
iterations that began in the window."""
from chipbench.harness import program_spans

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    lo, hi = program_spans.window(ctx)
    # an iteration that began in the window may end after it
    spans = program_spans.on_thread_of(
        program_spans.resident(None, lo, float("inf")), "engine.iteration")
    iterations = [s for s in spans
                  if s["name"] == "engine.iteration" and s["t_begin"] <= hi]
    emits = [s for s in spans
             if s["name"] == "engine.emit" and s["t_begin"] <= hi]
    if not iterations or not emits:
        return None
    host_s = sum(program_spans.self_time(i, spans) for i in iterations) \
        + sum(map(program_spans.duration, emits))
    return 1e3 * host_s / len(iterations)
