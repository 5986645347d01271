"""Share of the traced stretch's device seconds that
``device_ms_per_step.*`` cannot place: ops without a word of the
vocabulary in their path, ops the train step's HLO does not have, and
the device seconds of every other program run in the stretch, which may
lie under the step's instruction names
(``program_table.step_components``)."""
from chipbench.harness import program_table

LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    return program_table.unscoped_pct(ctx)
