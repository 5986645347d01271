"""Share of the traced window in which the device was idle while the
engine's thread was inside a decode call (``model.step`` and its
dispatch and readback, ``model.verify``, ``engine.draft``).  With
``idle_pct.admission`` and ``idle_pct.engine_host`` it partitions
``device_idle_pct.serve``."""
from chipbench.harness import program_spans

LAYER = "device"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def read(ctx):
    return program_spans.idle_pct(ctx, "decode_call")
