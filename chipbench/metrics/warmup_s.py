"""Seconds the cell's warm-up took (benchmark clock): compiling on a
cold start, tracing + lowering + loading from the cache on a warm one."""
LAYER = "process start"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(ctx):
    return ctx["readings"].get("warmup_s")
