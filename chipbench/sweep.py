"""The rate sweep for a serving cell — the builder's tool, not the
driver's command; it prints a table and no contract line.

    python chipbench/sweep.py --workload gpt2_774m.serve_chat --seed 1

One process: loads and warms up once, then offers the cell's mix at
rates rising by the cell's ``sweep.factor`` for ``sweep.seconds`` each
(after the mix's ramp), cancelling what is left between rates.  The
knee is the highest rate at which the backlog (submitted, not yet
admitted) at the end of its window is at most ``max_slots`` and nothing
failed.  The cell's ``traffic.rate_per_s`` is then set by hand to 0.8 x
or 1.25 x that rate.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run                       # noqa: E402
from chipbench.harness import traffic           # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    run.place_compile_cache()
    found = run.resolve(run.ROOT, args.workload)
    run.require_tpu(found["chips"])
    cell, config, job = found["cell"], found["config"], found["job"]
    plan, vocab = cell["sweep"], config["arch"]["vocab"]
    server, engine, _, warmup_s = job.build_server(config, cell, args.seed)
    print(f"# {args.workload}: warm-up {warmup_s:.1f} s, "
          f"{engine.warmed} programs; {plan}", flush=True)
    print("offered_rps completed_rps tokens_per_s backlog_end failed "
          "ttft_p50_ms ttft_p95_ms itl_p50_ms itl_p95_ms lag_p95_ms "
          "decode_batch_mean decode_step_ms prefill_ms kv_bucket",
          flush=True)
    try:
        for i in range(plan["steps"]):
            rate = plan["start_rate_per_s"] * plan["factor"] ** i
            # judged on the backlog, so what is left is always cancelled
            mix = dict(cell["traffic"], rate_per_s=rate,
                       at_window_end="cancel")
            out = job.offer(server, engine, mix, plan["seconds"],
                            args.seed + i, vocab)
            seen, d = job.summarize(out["loop"], mix, plan["seconds"]), \
                out["delta"]
            pct = traffic.percentile
            row = [rate, seen["completed_per_s"], seen["tokens_per_s"],
                   out["backlog"], seen["failed"],
                   pct(seen["ttft_ms"], 0.5), pct(seen["ttft_ms"], 0.95),
                   pct(seen["itl_ms"], 0.5), pct(seen["itl_ms"], 0.95),
                   pct(seen["lag_ms"], 0.95),
                   d["decode_tokens"] / max(1, d["iterations"]),
                   1e3 * d["decode_s"] / max(1, d["decode_n"]),
                   1e3 * d["prefill_s"] / max(1, d["prefill_n"]),
                   int(engine.cache.bucket)]
            print(" ".join(f"{v:.2f}" if isinstance(v, float) else str(v)
                           for v in row), flush=True)
            if seen["errors"]:
                print("# errors:", json.dumps(seen["errors"]), flush=True)
    finally:
        server.stop()


if __name__ == "__main__":
    main()
