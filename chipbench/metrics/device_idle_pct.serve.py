"""Share of the traced window in which no operation ran on the device
(mean over the cell's chips)."""
from chipbench.harness import trace_reduce

LAYER = "device"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    red = ctx["reduction"]
    return None if red is None else trace_reduce.idle_pct(red)
