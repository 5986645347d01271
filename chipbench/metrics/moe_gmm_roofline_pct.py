"""The grouped expert product's share of its roofline in prefill: the
least time the chip could take for the prefills of the traced stretch
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, by
``harness/moe_bytes.gmm_flops_and_bytes`` from what each ``model.prefill``
span says of its routing) over the device time of the ``megablox``
grouped-matmul kernels in the trace.  With ~1 routed row a token here
and 16 experts' matrices to read, the bytes bound it.  None where no
prefill ran the kernel in the stretch, and on a program without it."""
import re

LAYER = "experts"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"

# parallel/moe.py's megablox.gmm calls as trace_reduce.op_name names
# them (looked at by hand, PR 32)
GMM_OP = re.compile(r"^gmm(\.\d+)?\[tpu_custom_call\]$")


def read(ctx):
    red, r = ctx["reduction"], ctx["readings"]
    if red is None or not r.get("gmm_flops"):
        return None
    secs = sum(s for name, (s, _) in red["ops"].items()
               if GMM_OP.match(name))
    if secs <= 0:
        return None
    least = max(r["gmm_flops"] / ctx["peaks"]["bf16_flops_per_s"],
                r["gmm_bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
