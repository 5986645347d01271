"""The fullest held expert's tokens over the mean, a decode step: the
mean over the ``model.step.readback`` spans that began in the window of
``expert_load_max`` x ``expert_slots`` / ``expert_assignments`` (steps
that routed nothing here are left out).  In a deployment the fullest
expert's chip is the straggler every other chip's exchange waits for;
on one chip it is the longest segment of the grouped product.  None for
a family without routed experts (``harness/expert_load.py``)."""
from chipbench.harness import expert_load

LAYER = "experts"
MOVES = "serve_tokens_per_s"
UNIT = "ratio"
SOURCE = "program_span"


def read(ctx):
    ratios = [a["expert_load_max"] * a["expert_slots"]
              / a["expert_assignments"]
              for a in expert_load.steps_in_window(ctx)
              if a["expert_assignments"]]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
