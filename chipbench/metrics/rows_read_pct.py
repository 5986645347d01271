"""Share of the rows cache's position blocks that the decode steps
launched in the window fetched: 100 x the sum of ``row_blocks`` over
the sum of ``row_blocks_all`` on the ``model.step.dispatch`` spans that
began in the window.  A family whose step reads by extent (the hybrid
family's ragged kernel over layer 17's rows: each slot's blocks up to
its position) says both on every launch; a step that reads the whole
bucket whatever the positions says neither, and so does the parent
commit: None there.  Lower is fewer bytes a step for the same tokens."""
from chipbench.harness import program_spans

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_span"


def share_read(spans):
    """100 x blocks read over blocks held, summed over the spans that
    carry both attributes; None where none does."""
    said = [s["attrs"] for s in spans
            if "row_blocks" in s.get("attrs", ())
            and "row_blocks_all" in s["attrs"]]
    held = sum(a["row_blocks_all"] for a in said)
    if not held:
        return None
    return 100.0 * sum(a["row_blocks"] for a in said) / held


def read(ctx):
    return share_read(program_spans.resident(
        "model.step.dispatch", *program_spans.window(ctx)))
