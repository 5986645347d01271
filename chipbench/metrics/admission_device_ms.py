"""Device milliseconds an admission takes in the traced stretch: the
seconds of the modules whose role is ``prefill``, ``cache_write``,
``cache_install`` or ``select`` over the runs of the prefill programs.
Beside ``admission_ms``, which is what an admission costs the loop."""
from chipbench.harness import program_table

LAYER = "KV cache"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return program_table.device_ms(ctx["reduction"], program_table.table(),
                                   program_table.ADMISSION_ROLES,
                                   "prefill")
