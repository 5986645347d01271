"""Device milliseconds a training step spends in the Pallas flash
attention kernels (forward and fused backward), from the trace."""
import re

LAYER = "kernels"
MOVES = "train_tokens_per_s_chip"
UNIT = "ms"
SOURCE = "device_trace"

# the Mosaic custom calls of ops/pallas/attention.py as
# trace_reduce.op_name names them; the flash kernels are the only Pallas
# kernels in the step (looked at by hand, PR 24)
FLASH_OP = re.compile(r"\[tpu_custom_call\]$")


def flash_seconds_per_step(ctx):
    red, steps = ctx["reduction"], ctx["readings"].get("traced_steps")
    if red is None or not steps:
        return None
    secs = sum(s for name, (s, _) in red["ops"].items()
               if FLASH_OP.search(name))
    return secs / steps if secs > 0 else None


def read(ctx):
    s = flash_seconds_per_step(ctx)
    return None if s is None else 1e3 * s
