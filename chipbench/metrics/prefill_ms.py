"""Mean host-clock milliseconds of a prompt's prefill call
(mxnet_gen_step_seconds{phase=prefill}, sum over count)."""
LAYER = "model step"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_counter"


def read(ctx):
    d = ctx["readings"].get("delta")
    if not d or not d["prefill_n"]:
        return None
    return 1e3 * d["prefill_s"] / d["prefill_n"]
