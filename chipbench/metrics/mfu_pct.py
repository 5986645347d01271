"""Model FLOP/s utilisation: the forward + backward FLOPs a token needs
(harness/flops.py, no recomputation) times tokens per second per chip,
over the chip's published bf16 peak."""
LAYER = "training step"
MOVES = "train_tokens_per_s_chip"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    per_token = ctx["readings"].get("train_flops_per_token")
    rate = ctx["end_to_end"].get("train_tokens_per_s_chip")
    if per_token is None or rate is None:
        return None
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
