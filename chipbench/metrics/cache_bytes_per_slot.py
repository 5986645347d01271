"""Device bytes the slot cache has allocated for one slot while the
trace was open: the program's gauge ``mxnet_gen_cache_bytes`` summed
over its kinds (rows, window, state), as the job's sampler read it
every 20 ms of the traced stretch, over the engine's slots.  A program
without the gauge reads 0 everywhere and the line leaves the metric
out."""
LAYER = "KV cache"
MOVES = "serve_tokens_per_s"
UNIT = "bytes"
SOURCE = "program_counter"


def read(ctx):
    r = ctx["readings"]
    if not r.get("max_slots") or not r.get("cache_bytes"):
        return None
    return r["cache_bytes"] / r["max_slots"]
