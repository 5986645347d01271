"""Share of the chip's peak memory bandwidth the decode program reaches
on the bytes a step MUST read: every weight once plus the K and V rows
the live sequences hold (harness/flops.py), over the decode program's
mean device time in the trace.  The program's dense attention reads the
whole bucket, not only the live rows; those extra bytes are not
required work and do not count."""
import re

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"

# serving/model.py's jitted ``_step`` on the trace's "XLA Modules" line
DECODE_PROGRAM = re.compile(r"^jit__step$")


def read(ctx):
    red, r = ctx["reduction"], ctx["readings"]
    if red is None or r.get("live_kv_rows") is None:
        return None
    runs = [(s, n) for name, (s, n) in red["programs"].items()
            if DECODE_PROGRAM.match(name)]
    secs, count = sum(s for s, _ in runs), sum(n for _, n in runs)
    if not count or secs <= 0:
        return None
    step_bytes = r["param_bytes"] + r["live_kv_rows"] * r["kv_row_bytes"]
    return 100.0 * step_bytes / (secs / count) \
        / ctx["peaks"]["hbm_bytes_per_s"]
