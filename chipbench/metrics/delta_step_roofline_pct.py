"""The delta-rule state kernel's share of its roofline in the decode
program of the delta-rule hybrid family: the least time the chip could
take to read and write, at the HBM peak, the float32 matrix state of
every live slot in every linear layer of the traced steps
(``harness/delta_bytes.delta_state_bytes``: 2 x 2 211 840 B a slot and
layer at the published widths, from the live slots the job's sampler
saw every 20 ms of the traced stretch; times the decode programs the
trace holds), over the device time of the kernel's calls in the trace.
The same bytes whatever implements the step.  About 8 operations a
state element: the bytes bound it.  None where the trace shows no such
call, and for a job that says no ``delta_state_bytes``."""
import re

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"

# ops/pallas/delta_step.py's pallas_call (``name=``) as
# trace_reduce.op_name names it
DELTA_OP = re.compile(r"^delta_step(\.\d+)?\[tpu_custom_call\]$")
# serving/delta.py's jitted ``_step`` on the trace's "XLA Modules" line
DECODE_PROGRAM = re.compile(r"^jit__step$")


def read(ctx):
    red, r = ctx["reduction"], ctx["readings"]
    if red is None or not r.get("delta_state_bytes"):
        return None
    secs = sum(s for name, (s, _) in red["ops"].items()
               if DELTA_OP.match(name))
    steps = sum(n for name, (_, n) in red["programs"].items()
                if DECODE_PROGRAM.match(name))
    if secs <= 0 or not steps:
        return None
    least = steps * r["delta_state_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
