"""Mean milliseconds of ``model.step.dispatch`` in the window: the two
uploads, the jitted decode call returning and the new KV buffers
installed — the host-serial part of ``decode_step_ms``."""
from chipbench.harness import program_spans

LAYER = "model step"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    return program_spans.mean_ms(ctx, "model.step.dispatch")
