"""Mean tokens a held expert a layer a decode step: the sum of
``expert_assignments`` over the sum of ``expert_slots`` (held experts x
layers) on the ``model.step.readback`` spans that began in the window.
It is the shape of the held experts' product: near 3 with 48 slots and
16 of 128 experts held, far under the ~240 at which an expert's product
stops being bound by reading its weights.  None for a family without
routed experts (``harness/expert_load.py``)."""
from chipbench.harness import expert_load

LAYER = "experts"
MOVES = "serve_tokens_per_s"
UNIT = "tokens"
SOURCE = "program_span"


def read(ctx):
    return expert_load.share(expert_load.steps_in_window(ctx),
                             "expert_assignments")
