"""How late the load generator ran: 95th percentile of sent minus due,
over the requests due inside the window.  A starved generator must not
be read as a fast server."""
from chipbench.harness import traffic

LAYER = "load generator"
MOVES = "ttft_p95_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    return traffic.percentile(ctx["readings"].get("lag_ms") or [], 0.95)
