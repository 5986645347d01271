"""Device milliseconds a run of the decode program takes in the traced
stretch: the "XLA Modules" events of the modules whose role in the
program's table is ``decode``, seconds over runs.  Beside
``decode_step_ms``, which is what a step cost the loop."""
from chipbench.harness import program_table

LAYER = "model step"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return program_table.device_ms(ctx["reduction"], program_table.table(),
                                   ("decode",), "decode")
