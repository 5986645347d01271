"""Loop steps a token costs in decoding: the sum of ``loop_steps`` over
the ``model.step.dispatch`` spans that began in the window, over their
count.  A looped family (``serving.loop``) says on every launch how many
times its stack of layers runs for the step's tokens: 4.0 at the
published ``early_exit_threshold`` of 1, under which every token makes
every step.  A change that lets tokens leave early moves it (and the
outputs with it); one that drops a pass unannounced still says 4 and is
caught by ``correct``.  None for a family that says no ``loop_steps``,
and on a commit before PR 35."""
from chipbench.harness import program_spans

LAYER = "model step"
MOVES = "serve_tokens_per_s"
UNIT = "steps"
SOURCE = "program_span"


def mean_loop_steps(spans):
    """Mean of ``loop_steps`` over the spans that carry it; None where
    none does."""
    said = [s["attrs"]["loop_steps"] for s in spans
            if "loop_steps" in s.get("attrs", ())]
    return sum(said) / len(said) if said else None


def read(ctx):
    return mean_loop_steps(program_spans.resident(
        "model.step.dispatch", *program_spans.window(ctx)))
