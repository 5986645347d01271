"""Mean milliseconds of one admission on the engine's thread, prefill to
the first token in the stream (``engine.prefill`` spans that began in
the window): the prefill program, the row write, the first-token select
and the lane bookkeeping.  Beside ``prefill_ms`` it shows what the row
write and the select cost."""
from chipbench.harness import program_spans

LAYER = "KV cache"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    return program_spans.mean_ms(ctx, "engine.prefill")
