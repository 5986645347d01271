"""Share of the decode steps launched in the window that the engine
launched AHEAD: before it had read the previous step's tokens back, fed
by that step's token array on the device.  100 x the
``model.step.dispatch`` spans that began in the window with a true
``ahead`` attribute over all of them.  Where it is high, the host's
dispatch, emit and bookkeeping run under the device's step and
``idle_pct.decode_call`` and ``idle_pct.engine_host`` fall; each
fall-back (a finish, an admission, a cancel) costs one serial step.
None on a program whose spans carry no such attribute."""
from chipbench.harness import program_spans

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def share_ahead(spans):
    """100 x spans with a true ``ahead`` over the spans that say either
    way; None where none does."""
    said = [s["attrs"]["ahead"] for s in spans
            if "ahead" in s.get("attrs", ())]
    if not said:
        return None
    return 100.0 * sum(1 for a in said if a) / len(said)


def read(ctx):
    return share_ahead(program_spans.resident(
        "model.step.dispatch", *program_spans.window(ctx)))
