"""The ragged decode-attention kernel's share of its roofline in a
looped family's decode program: the least time the chip could take to
read the live K and V of the traced steps (``harness/loop_bytes.
attn_bytes``: rows ``0 .. pos`` of every live slot in every entry of the
cache, K and V, to the position and not to the kernel's 512-position
block, so the same work whatever implements the read; the job's sampler
took the positions every 20 ms of the traced stretch; times the decode
programs the trace holds) at the HBM peak, over the device time of the
kernel's calls in the trace.  One query a head: the bytes bound it, the
FLOPs (4 a K/V element) are two orders below.  Only the decode program
calls the kernel (prefill is dense or the flash kernel).  None where
the trace shows no such call, and for a job that says no ``attn_bytes``.
"""
import re

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"

# ops/pallas/decode_attention.py's pallas_call (``name=``) as
# trace_reduce.op_name names it
RAGGED_OP = re.compile(r"^ragged_attention(\.\d+)?\[tpu_custom_call\]$")
# serving/model.py's jitted ``_step`` on the trace's "XLA Modules" line
DECODE_PROGRAM = re.compile(r"^jit__step$")


def read(ctx):
    red, r = ctx["reduction"], ctx["readings"]
    if red is None or not r.get("attn_bytes"):
        return None
    secs = sum(s for name, (s, _) in red["ops"].items()
               if RAGGED_OP.match(name))
    steps = sum(n for name, (_, n) in red["programs"].items()
                if DECODE_PROGRAM.match(name))
    if secs <= 0 or not steps:
        return None
    least = steps * r["attn_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
