"""Mean host-clock milliseconds of a decode step, dispatch to tokens on
the host (mxnet_gen_step_seconds{phase=decode}, sum over count)."""
LAYER = "model step"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_counter"


def read(ctx):
    d = ctx["readings"].get("delta")
    if not d or not d["decode_n"]:
        return None
    return 1e3 * d["decode_s"] / d["decode_n"]
