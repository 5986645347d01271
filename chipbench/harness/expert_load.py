"""The held experts' load as the program says it: the attributes
``expert_assignments``, ``experts_hit``, ``expert_load_max`` and
``expert_slots`` (held experts x layers) that ``serving.moe`` puts on
each ``model.step.readback`` span, one decode step a span.  A family
without routed experts, and a commit before PR 32, say none of them:
the lists below are then empty and every reader returns None."""
from chipbench.harness import program_spans


def said(spans):
    """The attributes of those of ``spans`` that carry the load."""
    return [s["attrs"] for s in spans
            if s.get("attrs", {}).get("expert_slots")]


def steps_between(lo, hi):
    """The load of each decode step read back in [lo, hi] (host
    clock)."""
    return said(program_spans.resident("model.step.readback", lo, hi))


def steps_in_window(ctx):
    return steps_between(*program_spans.window(ctx))


def share(steps, name):
    """Sum of ``name`` over the sum of ``expert_slots``; None without
    steps."""
    if not steps:
        return None
    return sum(a[name] for a in steps) / sum(a["expert_slots"]
                                             for a in steps)
