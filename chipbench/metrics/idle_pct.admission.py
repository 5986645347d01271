"""Share of the traced window in which the device was idle while the
engine's thread was inside an admission (``engine.prefill`` and what
runs under it: ``model.prefill``, ``kv.write_prompt``,
``model.select``)."""
from chipbench.harness import program_spans

LAYER = "device"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def read(ctx):
    return program_spans.idle_pct(ctx, "admission")
