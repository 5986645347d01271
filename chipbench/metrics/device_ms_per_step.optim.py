"""Device milliseconds a traced training step spends in the component
``optim`` of the model (``mxnet_tpu.tracing.COMPONENTS``): the reduction's
ops looked up in the train step's own optimized HLO
(``harness/program_table.py``).  A fusion that holds a matmul counts
with the matmul, so a weight gradient with the optimizer's update fused
behind it is its layer's, and ``optim`` is what the optimizer runs on
its own; ``other`` is every other component (embed, head, loss, a norm
that stands alone)."""
from chipbench.harness import program_table

LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return program_table.component_ms_per_step(ctx, "optim")
