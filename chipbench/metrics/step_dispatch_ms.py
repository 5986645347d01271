"""Median milliseconds ``trainer.step()`` took to return (placing the
batch and dispatching the compiled step, no wait for the device)."""
import statistics

LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    times = ctx["readings"].get("dispatch_s")
    return 1e3 * statistics.median(times) if times else None
