"""Mean milliseconds of ``step.place`` in the window: the batch's arrays
committed to the mesh."""
from chipbench.harness import program_spans

LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    # no metric of this cell reads the table: it is written to stderr for
    # PERF.md section 5
    program_spans.idle_table(ctx, "spmd.step")
    return program_spans.mean_ms(ctx, "step.place")
