"""Mean milliseconds of ``step.dispatch`` in the window: the compiled
step's call returning (enqueued, not run)."""
from chipbench.harness import program_spans

LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    return program_spans.mean_ms(ctx, "step.dispatch")
