"""Seconds the XLA backend took to hand the process its compiled
programs: compiling on a cold start, loading executables from jax's
persistent cache on a warm one (the same jax event; the table books
it as ``compile_s`` or ``load_s``), summed over the table's records
WITH a role."""
from chipbench.harness import program_table

LAYER = "process start"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(ctx):
    return program_table.stage_seconds(program_table.table(), "compile", "load")
