"""The flash kernels' share of their roofline: the least time the chip
could take for a step's attention (the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s, harness/flops.py) over the time the trace
shows.  At b16 x 512 x 1024 wide the FLOPs bound it: 6.3 ms against
5.9 ms for the bytes."""
from chipbench.metrics import flash_ms_per_step

LAYER = "kernels"
MOVES = "train_tokens_per_s_chip"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    took = flash_ms_per_step.flash_seconds_per_step(ctx)
    fl = ctx["readings"].get("flash_flops_per_step")
    by = ctx["readings"].get("flash_bytes_per_step")
    if took is None or fl is None or by is None:
        return None
    least = max(fl / ctx["peaks"]["bf16_flops_per_s"],
                by / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took
