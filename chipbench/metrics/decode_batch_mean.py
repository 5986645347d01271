"""Mean sequences a decode iteration advanced: decode tokens over
engine iterations, both as the program counts them over the window."""
LAYER = "scheduler"
MOVES = "serve_tokens_per_s"
UNIT = "slots"
SOURCE = "program_counter"


def read(ctx):
    d = ctx["readings"].get("delta")
    if not d or not d["iterations"]:
        return None
    return d["decode_tokens"] / d["iterations"]
