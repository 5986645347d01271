"""KV-cache migrations to a larger bucket inside the window
(mxnet_gen_kv_migrations_total)."""
LAYER = "KV cache"
MOVES = "serve_tokens_per_s"
UNIT = "count"
SOURCE = "program_counter"


def read(ctx):
    d = ctx["readings"].get("delta")
    return None if not d else d["kv_migrations"]
