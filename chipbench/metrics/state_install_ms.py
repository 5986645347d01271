"""Mean milliseconds of one admission's install of fixed-size state
(recurrent state, conv tail, window rows) into its slot:
``cache.install_state`` spans that began in the window.  Beside
``admission_ms`` it shows what the kinds other than K/V rows cost."""
from chipbench.harness import program_spans

LAYER = "KV cache"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    return program_spans.mean_ms(ctx, "cache.install_state")
