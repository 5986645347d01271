"""Share of the held experts (over layers) that a decode step routed at
least one token to: 100 x the sum of ``experts_hit`` over the sum of
``expert_slots`` on the ``model.step.readback`` spans that began in the
window.  It sets the expert weight bytes a step has to read
(``harness/moe_bytes.py``); by chance 1 - (1 - 8 / 128)^slots.  None for
a family without routed experts (``harness/expert_load.py``)."""
from chipbench.harness import expert_load

LAYER = "experts"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def read(ctx):
    hit = expert_load.share(expert_load.steps_in_window(ctx), "experts_hit")
    return None if hit is None else 100.0 * hit
