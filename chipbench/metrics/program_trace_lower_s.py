"""Seconds the process spent tracing its compiled programs in Python
and lowering them to MLIR modules (jax's own duration events, booked
in the program table by program): the sum over the table's records
WITH a role, so the benchmark's reference programs and the eager
initialisers are left out.  The part of a warm start that no
persistent cache takes away."""
from chipbench.harness import program_table

LAYER = "process start"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(ctx):
    return program_table.stage_seconds(program_table.table(), "trace", "lower")
