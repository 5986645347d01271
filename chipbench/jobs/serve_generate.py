"""Job kind ``serve_generate``: a zoo GPT behind the continuous-batching
engine, built as tools/serve.py builds it (``DecodeModel.from_block`` ->
``GenerationEngine`` -> ``GenerationServer``) and driven in-process
through ``GenerationServer.generate()`` by open-loop traffic.  The
stdlib HTTP front end is bypassed.

Cell file keys: ``engine`` (GenerationEngine keywords), ``traffic`` (the
mix: rate_per_s, ramp_s, prompt / output length distributions,
``at_window_end`` "drain" or "cancel", drain_s), ``check``
(prompt_lengths, new_tokens, reference_length), ``trace_at_s``,
``trace_window_s``.
Configuration keys: ``zoo``, ``zoo_args``, ``zoo_kwargs``,
``serve_dtype`` and the ``arch`` group.
"""
import importlib
import time

import numpy as np

from chipbench.harness import flops, reference, trace_reduce, traffic

# System (float32 parameters; on a TPU its matmuls run at XLA's default
# precision, one bf16 pass) against the float32 reference at precision
# "highest", on last-token logits: max |a - b| over max |b|.  The v5e
# measured 0.0086-0.0107 over eight runs (my chip runs, PR 24); 0.05
# flags a wrong operation, a wrong cache row or a wrong position, not a
# rounding.  A greedy token is held to the reference's argmax only where
# the reference's two largest logits differ by more than LOGIT_TOL x
# max |logit| (4-9 of the 16 positions), because with random weights
# the largest logit changes on rounding.
LOGIT_TOL = 0.05


def build_server(config, cell, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    module, fn = config["zoo"].split(":")
    mx.random.seed(seed % (2 ** 31))
    net = getattr(importlib.import_module(module), fn)(
        *config["zoo_args"], **config["zoo_kwargs"])
    net.initialize()
    net(mx.np.zeros((1, 4), dtype="int32"))
    if config["serve_dtype"] != "float32":
        net.cast(config["serve_dtype"])
    model = serving.DecodeModel.from_block(net)
    engine = serving.GenerationEngine(model, **cell["engine"])
    t = time.perf_counter()
    server = serving.GenerationServer(engine=engine, warmup=True).start()
    return server, engine, model, time.perf_counter() - t


def check_against_reference(server, engine, model, cell, rng, vocab):
    """Prefill logits, greedy decoding through the cache, and a request
    alone against the same request in a full batch, all held to the
    reference's full forward pass."""
    import jax
    import jax.numpy as jnp
    spec = cell["check"]
    gelu_approx, eps = model.ga
    ref_len, n_new = spec["reference_length"], spec["new_tokens"]

    @jax.jit
    def ref_logits(params, ids):
        return reference.lm_logits(params, reference.hidden_states(
            params, ids, num_heads=model.num_heads, causal=True,
            pre_ln=True, eps=eps, gelu_approx=gelu_approx))

    def ref(tokens):
        # causal: right-padding changes no earlier position, so one
        # program of one length serves every check
        ids = np.zeros((1, ref_len), np.int32)
        ids[0, :len(tokens)] = tokens
        return np.asarray(ref_logits(model.params, ids)[0, :len(tokens)])

    prompts = [rng.integers(0, vocab, n, dtype=np.int32)
               for n in spec["prompt_lengths"]]
    prefill_err = []
    for p in prompts:
        bucket = min(b for b in engine.prompt_buckets if b >= len(p))
        got = np.asarray(model.prefill(p, bucket)[0], np.float32)
        want = ref(p)[-1]
        prefill_err.append(float(np.abs(got - want).max()
                                 / np.abs(want).max()))

    def greedy(p):
        return server.generate(p, max_new_tokens=n_new, method="greedy")

    alone = greedy(prompts[0]).result()
    want = ref(np.concatenate([prompts[0], alone[:-1]]))[len(prompts[0]) - 1:]
    top2 = np.sort(want, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > LOGIT_TOL * np.abs(want).max()
    decode_ok = all(t == a for t, a, d in
                    zip(alone, want.argmax(-1), decisive) if d)

    # the same request inside a full batch of short prompts (all in the
    # smallest KV bucket, so the decode program is the same one)
    others = [rng.integers(0, vocab, int(n), dtype=np.int32)
              for n in rng.integers(16, 180, engine.max_slots - 1)]
    streams = [greedy(p) for p in [prompts[0]] + others]
    batched = [s.result() for s in streams][0]
    diff = next((i for i, (a, b) in enumerate(zip(alone, batched))
                 if a != b), None)
    batch_ok = len(batched) == len(alone) and (
        diff is None or not decisive[diff])
    return {
        "prefill_logit_err": prefill_err,
        "decisive_positions": int(decisive.sum()), "decode_ok": decode_ok,
        "first_batch_difference": diff, "batch_ok": batch_ok,
        "ok": bool(max(prefill_err) <= LOGIT_TOL and decode_ok
                   and batch_ok and len(alone) == n_new),
    }


def counters():
    from mxnet_tpu import metrics
    dec = metrics.hist_stats("mxnet_gen_step_seconds", phase="decode")
    pre = metrics.hist_stats("mxnet_gen_step_seconds", phase="prefill")
    return {
        "compiles": metrics.COMPILE_MISSES.value
        + metrics.COMPILE_PERSISTENT_HITS.value,
        "decode_tokens": metrics.value("mxnet_gen_tokens_total",
                                       phase="decode"),
        "iterations": metrics.value("mxnet_gen_iterations_total"),
        "kv_migrations": metrics.value("mxnet_gen_kv_migrations_total"),
        "decode_s": dec[0], "decode_n": dec[1],
        "prefill_s": pre[0], "prefill_n": pre[1],
    }


def offer(server, engine, mix, seconds, seed, vocab, trace=None):
    """Offer ``mix`` for ``seconds`` (after its ramp) and return the
    open loop, the counters' movement over the window and, where
    ``trace`` = (at_s, window_s) is given, the trace's reduction and the
    mean live KV rows during it."""
    events = []
    loop = traffic.OpenLoop(
        lambda prompt, n: server.generate(prompt, max_new_tokens=n,
                                          method="greedy"),
        traffic.schedule(mix, seconds, seed, vocab),
        on_event=lambda ns, n: events.append(
            (ns, "engine_loop" if n else "waiting_for_request")))
    t0 = time.perf_counter() + float(mix["ramp_s"]) + 0.05
    loop.start(t0)

    def sleep_until(t):
        time.sleep(max(0.0, t0 + t - time.perf_counter()))

    sleep_until(0.0)
    before = counters()
    reduction, live_rows = None, []
    if trace is not None:
        at_s = min(trace[0], max(0.0, seconds - trace[1]) / 2)
        sleep_until(at_s)
        with trace_reduce.TraceWindow() as tw:
            end = time.perf_counter() + min(trace[1], seconds)
            while time.perf_counter() < end:
                live_rows.append(int(np.maximum(
                    engine.cache.positions, 0).sum()))
                time.sleep(0.02)
        reduction = tw.reduction()
    sleep_until(seconds)
    after = counters()
    backlog = len(engine.scheduler)
    loop.stop_sending()
    if mix["at_window_end"] == "cancel":
        loop.cancel_unfinished()
        loop.join(10.0)
    elif not loop.join(float(mix["drain_s"])):
        loop.cancel_unfinished()
        loop.join(10.0)
    return {
        "t0": t0, "loop": loop, "delta": {k: after[k] - before[k] for k in after},
        "backlog": backlog, "reduction": reduction, "events": events,
        "live_rows": float(np.mean(live_rows)) if live_rows else None,
    }


def summarize(loop, mix, seconds):
    """What the clients saw.  Tails are over the requests DUE inside the
    window; tokens are those received inside it."""
    reqs = loop.requests
    in_window = [r for r in reqs if 0.0 <= r.due_s < seconds
                 and r.sent_s is not None]
    tokens = sum(1 for r in reqs for t in r.token_s if 0.0 <= t <= seconds)
    ttft = [1e3 * (r.token_s[0] - r.due_s) for r in in_window if r.token_s]
    itl = [1e3 * (b - a) for r in in_window
           for a, b in zip(r.token_s, r.token_s[1:])]
    lag = [1e3 * (r.sent_s - r.due_s) for r in in_window]
    if mix["at_window_end"] == "cancel":
        # above capacity the backlog is cancelled at the end: a request
        # counts when it finished or failed inside the window
        attempted = [r for r in reqs if r.ended_s is not None
                     and r.ended_s <= seconds
                     and (r.finished or r.error is not None)]
        failed = [r for r in attempted if r.error is not None]
    else:
        attempted = in_window
        failed = [r for r in attempted if not r.finished]
    return {
        "attempted": len(attempted), "failed": len(failed),
        "tokens_per_s": tokens / seconds,
        "completed_per_s": sum(1 for r in reqs if r.finished
                               and 0.0 <= r.ended_s <= seconds) / seconds,
        "ttft_ms": ttft, "itl_ms": itl, "lag_ms": lag,
        "errors": sorted({repr(r.error)[:200] for r in reqs
                          if r.error is not None})[:5],
    }


def run(ctx):
    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    arch, mix, seconds = config["arch"], cell["traffic"], ctx["seconds"]
    rng = np.random.default_rng(seed)
    server, engine, model, warmup_s = build_server(config, cell, seed)
    try:
        from mxnet_tpu import metrics
        compiled = int(metrics.COMPILE_MISSES.value)
        loaded = int(metrics.COMPILE_PERSISTENT_HITS.value)
        check = check_against_reference(server, engine, model, cell, rng,
                                        arch["vocab"])
        out = offer(server, engine, mix, seconds, seed, arch["vocab"],
                    (cell["trace_at_s"], cell["trace_window_s"])
                    if ctx["trace"] else None)
    finally:
        server.stop()
    seen, delta, red = summarize(out["loop"], mix, seconds), out["delta"], \
        out["reduction"]
    breakdown = None
    if red is not None:
        breakdown = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": trace_reduce.gaps_by_phase(
                red["gaps"], out["events"], red["offset_ns"]),
        }
    import jax
    itemsize = np.dtype(config["serve_dtype"]).itemsize
    return {
        "correct": check["ok"],
        "attempted": seen["attempted"],
        "failed": seen["failed"],
        "compiled_in_window": int(delta["compiles"]),
        "end_to_end": {
            "setup_s": out["t0"] - ctx["t_proc"],
            "serve_tokens_per_s": seen["tokens_per_s"],
            "ttft_p95_ms": traffic.percentile(seen["ttft_ms"], 0.95),
            "itl_p95_ms": traffic.percentile(seen["itl_ms"], 0.95),
        },
        "readings": {
            "warmup_s": warmup_s,
            "delta": delta,
            "lag_ms": seen["lag_ms"],
            "live_kv_rows": out["live_rows"],
            "param_bytes": sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(model.params)),
            "kv_row_bytes": flops.decode_step_bytes(
                0, 1, arch["layers"], arch["width"], itemsize),
        },
        "trace": red,
        "breakdown": breakdown,
        "notes": {
            "check": check, "warmup_s": warmup_s,
            "programs_warmed": engine.warmed,
            "programs_compiled": compiled, "programs_loaded": loaded,
            "requests": len(out["loop"].requests),
            "completed_per_s": seen["completed_per_s"],
            "backlog_at_end": out["backlog"],
            "ttft_ms_p50": traffic.percentile(seen["ttft_ms"], 0.5),
            "ttft_ms_p95": traffic.percentile(seen["ttft_ms"], 0.95),
            "itl_ms_p50": traffic.percentile(seen["itl_ms"], 0.5),
            "itl_ms_p95": traffic.percentile(seen["itl_ms"], 0.95),
            "generator_lag_ms_p95": traffic.percentile(seen["lag_ms"],
                                                       0.95),
            "kv_bucket_at_end": int(engine.cache.bucket),
            "errors": seen["errors"],
        },
    }
