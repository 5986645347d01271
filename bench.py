"""Benchmark driver hook: prints one JSON line PER HEADLINE CONFIG.

Default invocation (no MXNET_BENCH_MODEL) runs the five headline
configs — BERT MLM, GPT, LSTM-PTB, ViT-B/16, then ResNet-50 LAST (the
driver parses the last line as the metric of record, keeping config 2
continuous with prior rounds).  Each model runs in a fresh subprocess
so HBM resets between configs.  Setting MXNET_BENCH_MODEL runs that
single config.

Config 2 (r05-era BASELINE.md, in git history): ResNet-50 ImageNet-shape
training throughput,
images/sec/chip — hybridized fwd+bwd+update as one compiled XLA program
(SPMDTrainer on a 1-chip mesh), Speedometer-style timing.

vs_baseline divides by the 300 img/s midpoint of that file's unverified
V100-fp32 sanity band (no verifiable reference numbers exist).

Env knobs: MXNET_BENCH_BATCH (default 128), MXNET_BENCH_STEPS (default 40),
MXNET_BENCH_MODEL (resnet50_v1|bert|gpt|lstm|vit),
MXNET_BENCH_BERT_ARCH (base|large — BASELINE row 3c), MXNET_BENCH_DTYPE
(default bfloat16), MXNET_BENCH_IMAGE (224), MXNET_BENCH_SEQLEN,
MXNET_BENCH_DATA (synthetic|recordio — recordio feeds the model through
the REAL IO stack: an im2rec-style pack read by the native C++
prefetcher, per-image random-crop+mirror augment, uint8 batches to the
device, normalize/NCHW/cast in-graph), MXNET_BENCH_RECORD_FMT (raw|jpg),
MXNET_BENCH_EAGER=1 (lstm/gpt only: run the NON-hybridized per-op
dispatch path through the lazy bulking engine — pair with
MXNET_BULK_MAX_OPS to compare bulked vs per-op dispatch), and
MXNET_BENCH_MODEL=bulk_smoke (the CI acceptance micro-run: >=1.3x
dispatch reduction + steady segment cache + loss parity), and
MXNET_BENCH_MODEL=dist_comm (overlapped-collectives ratios on the
calibrated synthetic wire: serialized vs optimizer-phase overlap vs
backward-streamed overlap, trended against recorded ROUND_BASELINES).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMG_S = 300.0  # midpoint of the r05-era sanity band (unverified)

# Recorded baselines for the r5-added headline configs (BENCH_r05 on
# this rig, 2026-08-02): until r5 these metrics printed vs_baseline 0.0
# (write-only) — now each round trends against the round that
# introduced them.  Keys must match the emitted metric names exactly;
# an unknown metric (changed batch/seqlen/dtype env) reports 0.0, which
# the driver reads as "no baseline", not a regression.
ROUND_BASELINES = {
    "bert_base_mlm_bfloat16_b48x512_train": 158535.0,
    "gpt2_124m_lm_bfloat16_b8x1024_train": 104679.8,
    "lstm_ptb_bfloat16_b128x35_train": 433096.2,
    "vit_b16_bfloat16_b128x224_train_throughput": 865.2,
    # generation-serving baselines (r7 on this rig, 2026-08-03):
    # serve_bench --generate at 8 clients (tiny CPU GPT). NOISY on the
    # shared-CPU rig (~±40% run-to-run); treat vs_baseline as a trend
    # indicator, not a gate. TTFT: vs_baseline < 1.0 is an improvement.
    "gen_serving_tokens_per_s": 1599.1,
    "gen_serving_ttft_ms_p50": 18.2,
    # the headline config (r5 plateau midpoint, BENCH_r02-r05): recorded
    # so bench.py --check can trend the metric of record too
    "resnet50_v1_bfloat16_b128_train_throughput": 2450.0,
    # overlapped-collectives ratios on the calibrated synthetic wire,
    # measured ad hoc on this rig (2026-08-04, MXNET_BENCH_MODEL=
    # dist_comm: optimizer-phase 1.47x with streaming pinned off,
    # backward-streamed 1.50-1.64x) — no checked-in BENCH round carries
    # them yet; the next recorded bench round lands them in its JSON.
    # PR 14 measured 1.35-1.67x for the optimizer-phase overlap but
    # never recorded it; these are RATIOS against a per-run-calibrated
    # wire, so rig noise largely divides out (~±15%).
    "dist_comm_overlap_ratio": 1.5,
    "dist_comm_backward_overlap_ratio": 1.6,
}

# Wall-clock numbers on this rig swing ±25-40% run-to-run (documented
# across BENCH_r02-r05 and the r7 gen baselines), so --check treats
# throughput deltas as trend WARNINGS, never failures; only the
# deterministic gates below (compile counts, flush counts, stall
# fraction) and the step-time gate can fail the check.
CHECK_NOISE_BAND = 0.40

# Per-model step-time baselines (BENCH_r06, 2026-08-04): the
# step_breakdown.step_s of each headline config's timed loop.  Promoted
# from warn-only to a GATED check: a round whose per-model step time
# lands past STEP_TIME_GATE_RATIO x the recorded baseline FAILS
# bench.py --check.  The band is deliberately generous — rig noise is
# ±25-40% run-to-run, and a fast-day baseline against a slow-day check
# compounds to ~2.3x — so only a real in-program regression (3x+ step
# time) can trip it while kernel wins stay held, not just landed.
# Each entry pairs the step_s baseline with the SAME run's headline
# value: the gate engages only when a round's own value lands within
# STEP_RIG_CLASS_WINDOW of the baseline's companion value (evidence of
# a comparable rig class).  A round from a different host class (the
# checked-in rounds span a ~600x rig spread) warns that the baseline
# needs re-recording instead of tripping a hard gate on hardware —
# absolute wall-clock across rig classes is exactly what this module
# refuses to gate.  A real in-program regression moves step_s ~2.5x
# and throughput ~2.5x, both well inside the 10x class window, so it
# still fails.
# r06 ran on a 2-core CPU container (see BENCH_r06.json's note): only
# lstm fit the compile+step budget there; the other headline configs'
# entries get recorded at the next full round on the bench rig, and
# until then those metrics stay warn-only (an absent entry skips the
# gate, it never fakes one).
STEP_BASELINES = {
    "lstm_ptb_bfloat16_b128x35_train": {"step_s": 6.4274,
                                        "value": 697.0},
}
STEP_TIME_GATE_RATIO = 2.5
STEP_RIG_CLASS_WINDOW = 10.0

# Deterministic regression gates for bench.py --check: these numbers do
# not move with host load, so a miss is a real regression, not noise.
CHECK_GATES = {
    # XLA compiles during the timed window of the --check micro-runs
    # (after warmup); any recompile in steady state is a regression
    "compiles_after_warmup": 0,
    # fraction of the prefetched micro-run's wall time the step loop
    # spent blocked on input with a loader FASTER than the step — the
    # pipeline must hide it (mxnet_prefetch_stall_seconds)
    "prefetch_stall_frac_max": 0.10,
    # bulked-dispatch steady state: segment flushes per step must not
    # grow between the first and second half of the timed loop
    "flush_growth_per_step": 0,
}


def _vs_baseline(metric: str, value: float) -> float:
    base = ROUND_BASELINES.get(metric)
    return round(value / base, 3) if base else 0.0


def _metrics_mark():
    """Snapshot the step-phase histogram sums before a timed loop."""
    from mxnet_tpu import metrics
    return (metrics.hist_stats("mxnet_step_data_seconds")[0],
            metrics.hist_stats("mxnet_step_dispatch_seconds")[0])


def _step_breakdown(mark, dt, steps):
    """Per-step {data, dispatch, sync} seconds over a timed loop of
    ``steps`` steps taking ``dt`` wall seconds.  data/dispatch come from
    the trainer's runtime-metrics histograms (mxnet_step_*_seconds);
    sync is the remainder — the device-execution tail the end-of-loop
    loss fetch blocks on.  The three components sum to dt/steps."""
    d1, p1 = _metrics_mark()
    data = max(d1 - mark[0], 0.0) / steps
    disp = max(p1 - mark[1], 0.0) / steps
    per = dt / steps
    return {"data_s": round(data, 6),
            "dispatch_s": round(disp, 6),
            "sync_s": round(max(per - data - disp, 0.0), 6),
            "step_s": round(per, 6)}


def bench_gen_serving() -> None:
    """Config 7 (ISSUE 7 satellite): continuous-batching generation
    SERVING throughput + TTFT — serve_bench --generate's numbers as
    round-JSON metric lines, so serving regressions trend against a
    recorded baseline instead of being write-only."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench
    rep = serve_bench.bench_generation(n_clients=8, reqs=2,
                                       new_tokens=24, max_slots=8)
    tps = float(rep["engine_tokens_per_s"])
    ttft = rep["ttft_ms_p50"]
    print(json.dumps({
        "metric": "gen_serving_tokens_per_s",
        "value": round(tps, 1), "unit": "tokens/sec",
        "vs_baseline": _vs_baseline("gen_serving_tokens_per_s", tps),
        "speedup_vs_oneshot": rep["speedup"],
        "clients": rep["clients"],
    }), flush=True)
    if ttft is not None:
        # latency: vs_baseline < 1.0 is an IMPROVEMENT for this metric
        print(json.dumps({
            "metric": "gen_serving_ttft_ms_p50",
            "value": float(ttft), "unit": "ms",
            "vs_baseline": _vs_baseline("gen_serving_ttft_ms_p50",
                                        float(ttft)),
            "ttft_ms_p95": rep["ttft_ms_p95"],
        }), flush=True)


def bench_dist_comm() -> None:
    """Config 8 (ISSUE 15 satellite): the overlapped-vs-serialized
    steps/sec ratios from the dist-comm smoke's calibrated synthetic
    wire, landed as round-JSON metric lines — PR 14 measured 1.35-1.67x
    but never recorded it, so future rounds trend both the
    optimizer-phase overlap (PR 14) and the backward-streaming overlap
    (ISSUE 15) against recorded baselines.  Ratios, not wall clocks:
    the wire is calibrated per run, so they are far less rig-noise
    sensitive than absolute throughput."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import dist_comm_smoke as dcs
    failures = []

    # the PR-14 update-heavy leg: serialized vs optimizer-phase
    # overlap, streaming + segmentation pinned off inside the shared
    # helper so this ratio isolates the PR-14 scheduler
    opt = dcs.optimizer_leg_ratio()
    print(json.dumps({
        "metric": "dist_comm_overlap_ratio",
        "value": round(opt["ratio"], 3), "unit": "x vs serialized",
        "vs_baseline": _vs_baseline("dist_comm_overlap_ratio",
                                    opt["ratio"]),
        "wire_ms_per_step": round(opt["wire_ms"], 1),
    }), flush=True)

    # the ISSUE-15 backward-streaming leg (its own calibration + the
    # optimizer-only comparison ride along in the report)
    rep = dcs.backward_leg(failures)
    print(json.dumps({
        "metric": "dist_comm_backward_overlap_ratio",
        "value": round(rep.get("ratio", 0.0), 3),
        "unit": "x vs serialized",
        "vs_baseline": _vs_baseline("dist_comm_backward_overlap_ratio",
                                    rep.get("ratio", 0.0)),
        "optimizer_only_ratio": round(rep.get("opt_ratio", 0.0), 3),
        "wire_ms_per_step": round(rep.get("wire_ms", 0.0), 1),
        "gates_failed": failures,
    }), flush=True)


def _check_input_pipeline(failures) -> dict:
    """--check gate A: a prefetched SPMD micro-fit with a loader FASTER
    than the step — steady state must show 0 XLA compiles and a near-
    zero input-stall fraction (the pipeline hides the loader)."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import metrics as _metrics
    from mxnet_tpu.io import DevicePrefetcher
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    mx.random.seed(0)
    net = mx.gluon.nn.Sequential()
    net.add(mx.gluon.nn.Dense(512, activation="relu"),
            mx.gluon.nn.Dense(256, activation="relu"),
            mx.gluon.nn.Dense(64))
    net.initialize()
    net(mx.np.zeros((2, 256)))
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {"learning_rate": 0.01},
                          mesh=make_mesh({"dp": 1},
                                         devices=jax.devices()[:1]),
                          rules=DATA_PARALLEL_RULES)

    def batch_fn(step):
        # ~1ms of host "preprocessing" — well under the step time, so
        # the prefetch thread must hide it completely
        time.sleep(0.001)
        rng = onp.random.RandomState(step)
        return (mx.np.array(rng.uniform(-1, 1, (256, 256)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (256, 64)).astype("f4")))

    warm = 4
    steps = int(os.environ.get("MXNET_BENCH_CHECK_STEPS", "16"))
    pf = DevicePrefetcher(batch_fn, depth=2)
    trainer.fit(pf, warm).asnumpy()              # warmup: compile
    c0 = _metrics.value("mxnet_compile_misses_total")
    s0 = _metrics.hist_stats("mxnet_prefetch_stall_seconds")[0]
    t0 = time.perf_counter()
    trainer.fit(pf, warm + steps).asnumpy()
    wall = time.perf_counter() - t0
    pf.close()
    compiles = _metrics.value("mxnet_compile_misses_total") - c0
    stall = _metrics.hist_stats("mxnet_prefetch_stall_seconds")[0] - s0
    stall_frac = stall / wall if wall > 0 else 0.0
    if compiles > CHECK_GATES["compiles_after_warmup"]:
        failures.append(
            f"input-pipeline: {compiles:.0f} XLA compiles after warmup "
            f"(gate {CHECK_GATES['compiles_after_warmup']})")
    if stall_frac > CHECK_GATES["prefetch_stall_frac_max"]:
        failures.append(
            f"input-pipeline: stall fraction {stall_frac:.3f} > "
            f"{CHECK_GATES['prefetch_stall_frac_max']} with a loader "
            "faster than the step — the prefetcher is not hiding input")
    return {"compiles_after_warmup": compiles,
            "stall_frac": round(stall_frac, 4),
            "steps_per_s": round(steps / wall, 1)}


def _check_dispatch_flush(failures) -> dict:
    """--check gate B: the bulked eager micro-loop's dispatch surface —
    segment flushes per step must be steady (no per-step growth) and
    steady state must not recompile."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, metrics as _metrics

    mx.random.seed(1)
    net = mx.gluon.nn.Sequential()
    net.add(mx.gluon.nn.Dense(32, activation="tanh"),
            mx.gluon.nn.Dense(8))
    net.initialize()
    net(mx.np.zeros((2, 16)))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=None)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randn(8, 16).astype("f4"))
    y = mx.np.array(rng.randint(0, 8, (8,)).astype("int32"))

    def flushes():
        return sum(_metrics.value("mxnet_bulk_segments_total", reason=r)
                   for r in ("host_read", "max_ops", "mutation",
                             "waitall", "autograd", "cross_thread",
                             "unjittable"))

    def run(n):
        for _ in range(n):
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(8)
            loss.asnumpy()

    run(4)                                        # warmup
    half = 8
    c0 = _metrics.value("mxnet_compile_misses_total")
    f0 = flushes()
    run(half)
    f1 = flushes()
    run(half)
    f2 = flushes()
    compiles = _metrics.value("mxnet_compile_misses_total") - c0
    growth = ((f2 - f1) - (f1 - f0)) / half
    if compiles > CHECK_GATES["compiles_after_warmup"]:
        failures.append(
            f"dispatch: {compiles:.0f} XLA compiles after warmup "
            f"(gate {CHECK_GATES['compiles_after_warmup']})")
    if growth > CHECK_GATES["flush_growth_per_step"]:
        failures.append(
            f"dispatch: segment flushes growing {growth:+.2f}/step in "
            "steady state (second half vs first half)")
    return {"compiles_after_warmup": compiles,
            "flushes_per_step": round((f2 - f1) / half, 2),
            "flush_growth_per_step": round(growth, 3)}


def bench_check(paths) -> None:
    """``bench.py --check [round.json ...]``: the bench regression gate.

    Deterministic regressions FAIL (exit 1): XLA compiles after warmup,
    segment-flush growth, input-stall fraction with prefetch on.
    Per-model STEP TIME is gated too (promoted from warn-only at r06):
    a round's step_breakdown.step_s past STEP_TIME_GATE_RATIO x its
    recorded STEP_BASELINES entry fails — the band is generous enough
    that rig noise cannot trip it, so a trip is an in-program
    regression.  Raw throughput deltas against ROUND_BASELINES still
    only WARN — this rig's run-to-run noise is ±25-40%
    (CHECK_NOISE_BAND), so a throughput dip is a trend signal for a
    human, not a gate."""
    failures = []
    report = {"input_pipeline": _check_input_pipeline(failures),
              "dispatch": _check_dispatch_flush(failures)}

    warnings = []
    records = []
    for path in paths:
        with open(path) as f:
            text = f.read()
        # two shapes: the driver's round file (one JSON object whose
        # "tail" string holds bench.py's JSONL output and whose
        # "parsed" object is the headline metric), or raw bench.py
        # JSONL.  Be liberal: collect every {"metric": ...} record we
        # can decode from either.
        lines = text.splitlines()
        try:
            doc = json.loads(text)
        except ValueError:
            doc = None
        if isinstance(doc, dict):
            if isinstance(doc.get("parsed"), dict):
                records.append(doc["parsed"])
            if "metric" in doc:
                # a bare one-record file IS the record (a single-line
                # bench JSONL parses as a whole-file JSON doc)
                records.append(doc)
            lines = str(doc.get("tail", "")).splitlines()
        for line in lines:
            line = line.strip().rstrip(",")
            if '"metric"' not in line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                records.append(rec)
    seen = set()
    for rec in records:
        name = rec.get("metric")
        # step-time gate: the one wall-clock number that FAILS (with
        # the generous band) — per-model step_s is the in-program cost
        # kernel work attacks, so losing it must stop the line
        bd = rec.get("step_breakdown")
        step_base = STEP_BASELINES.get(name)
        if isinstance(bd, dict) and step_base:
            step_s = bd.get("step_s")
            val = rec.get("value")
            same_class = (
                isinstance(val, (int, float)) and val > 0
                and step_base["value"] / STEP_RIG_CLASS_WINDOW
                <= val <= step_base["value"] * STEP_RIG_CLASS_WINDOW)
            if isinstance(step_s, (int, float)) \
                    and (name, "step", step_s) not in seen:
                seen.add((name, "step", step_s))
                sratio = step_s / step_base["step_s"]
                if not same_class:
                    warnings.append(
                        f"step-time gate SKIPPED for {name}: the "
                        f"round's throughput ({val}) is outside "
                        f"{STEP_RIG_CLASS_WINDOW:.0f}x of the "
                        f"baseline's rig ({step_base['value']}) — "
                        "different host class; re-record "
                        "STEP_BASELINES on the current rig")
                elif sratio > STEP_TIME_GATE_RATIO:
                    failures.append(
                        f"step-time: {name} step_s {step_s:.4f} is "
                        f"{sratio:.2f}x the recorded baseline "
                        f"{step_base['step_s']:.4f} (gate "
                        f"{STEP_TIME_GATE_RATIO}x)")
                elif sratio > 1 + CHECK_NOISE_BAND:
                    warnings.append(
                        f"step-time within the gate but beyond noise: "
                        f"{name} step_s {step_s:.4f} = {sratio:.2f}x "
                        f"baseline {step_base['step_s']:.4f}")
        value = rec.get("value")
        base = ROUND_BASELINES.get(name)
        if not base or not isinstance(value, (int, float)) \
                or (name, value) in seen:
            continue      # a round file's "parsed" duplicates its tail
        seen.add((name, value))
        ratio = value / base
        lat = "ttft" in str(name) or str(rec.get("unit", ""))\
            .endswith("ms")
        worse = ratio > 1 + CHECK_NOISE_BAND if lat \
            else ratio < 1 - CHECK_NOISE_BAND
        drift = ratio > 1.0 if lat else ratio < 1.0
        if worse:
            warnings.append(
                f"WALL-CLOCK beyond the ±{CHECK_NOISE_BAND:.0%} "
                f"noise band: {name} = {value} vs baseline {base} "
                f"({ratio:.2f}x) — investigate, but wall-clock "
                "never fails the gate")
        elif drift:
            warnings.append(
                f"wall-clock within noise: {name} = {value} vs "
                f"baseline {base} ({ratio:.2f}x)")
    for w in warnings:
        sys.stderr.write(f"[bench --check] warn: {w}\n")
    print(json.dumps({"metric": "bench_check", "ok": not failures,
                      "warnings": len(warnings), **report}))
    if failures:
        raise SystemExit("bench --check FAILED: " + "; ".join(failures))


def bench_bert(batch: int, steps: int, dtype: str, seq_len: int) -> None:
    """Config 3: BERT-base MLM step throughput, tokens/sec/chip."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import get_bert
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    vocab = 30522
    n_mask = max(1, int(seq_len * 0.15))     # standard 15% MLM masking
    arch = os.environ.get("MXNET_BENCH_BERT_ARCH", "base")
    arches = {"base": "bert_12_768_12", "large": "bert_24_1024_16"}
    if arch not in arches:
        raise SystemExit(f"MXNET_BENCH_BERT_ARCH={arch!r}: "
                         f"choose from {sorted(arches)}")
    arch_name = arches[arch]
    mx.random.seed(0)
    net = get_bert(arch_name, vocab_size=vocab, dropout=0.0,
                   max_length=max(512, seq_len),
                   use_pooler=False, use_decoder=True,
                   use_classifier=False)
    net.initialize()
    net(mx.np.zeros((2, 32), dtype="int32"),
        mx.np.zeros((2, 32), dtype="int32"),
        mx.np.full((2,), 32, dtype="int32"),
        mx.np.zeros((2, 4), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(
        net, lambda logits, labels: loss_fn(logits, labels),
        optimizer="adamw", optimizer_params={"learning_rate": 1e-4},
        mesh=mesh, rules=DATA_PARALLEL_RULES,
        # loss reads the MLM vocab logits (last forward output)
        output_transform=lambda out: out[-1])
    rng = onp.random.RandomState(0)
    x = [mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                     .astype("int32")),
         mx.np.array(onp.zeros((batch, seq_len), dtype="int32")),
         mx.np.array(onp.full((batch,), seq_len, dtype="int32")),
         mx.np.array(rng.randint(0, seq_len, (batch, n_mask))
                     .astype("int32"))]
    y = mx.np.array(rng.randint(0, vocab, (batch, n_mask))
                    .astype("int32"))
    multistep = int(os.environ.get("MXNET_BENCH_MULTISTEP", "0"))
    if multistep:
        # K steps fused into one lax.scan program (run_steps): no
        # per-step dispatch inside the timed region
        xk = [mx.np.array(onp.broadcast_to(
            a.asnumpy(), (multistep,) + tuple(a.shape)).copy())
            for a in x]
        yk = mx.np.array(onp.broadcast_to(
            y.asnumpy(), (multistep,) + tuple(y.shape)).copy())
        trainer.run_steps(xk, yk).asnumpy()
        trainer.run_steps(xk, yk).asnumpy()
        n_calls = max(1, steps // multistep)
        m0 = _metrics_mark()
        t0 = time.perf_counter()
        for _ in range(n_calls):
            losses = trainer.run_steps(xk, yk)
        losses.asnumpy()
        dt = time.perf_counter() - t0
        breakdown = _step_breakdown(m0, dt, multistep * n_calls)
        tok_s = batch * seq_len * multistep * n_calls / dt
    else:
        # two warmup steps: the first compiles, the second recompiles
        # with the donated buffers' optimized on-device layouts
        float(trainer.step(x, y).asnumpy())
        float(trainer.step(x, y).asnumpy())
        m0 = _metrics_mark()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(x, y)
        loss.asnumpy()
        dt = time.perf_counter() - t0
        breakdown = _step_breakdown(m0, dt, steps)
        tok_s = batch * seq_len * steps / dt
    name = f"bert_{arch}_mlm_{dtype}_b{batch}x{seq_len}_train"
    print(json.dumps({
        "metric": name,
        "value": round(tok_s, 1), "unit": "tokens/sec/chip",
        # the baseline was recorded on the per-step path; a MULTISTEP
        # run measures a different configuration under the same name
        "vs_baseline": 0.0 if multistep else _vs_baseline(name, tok_s),
        "step_breakdown": breakdown}))


def bench_gpt(batch: int, steps: int, dtype: str, seq_len: int) -> None:
    """GPT-2-124M causal-LM step throughput, tokens/sec/chip
    (beyond-reference config; flash attention engages for long seqs)."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import get_gpt
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    vocab = 50257
    mx.random.seed(0)
    net = get_gpt("gpt2_124m", vocab_size=vocab, dropout=0.0,
                  max_length=max(1024, seq_len))
    net.initialize()
    net(mx.np.zeros((2, 16), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(net, lambda o, l: loss_fn(o, l),
                          optimizer="adamw",
                          optimizer_params={"learning_rate": 1e-4},
                          mesh=mesh, rules=DATA_PARALLEL_RULES)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    y = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    float(trainer.step(x, y).asnumpy())
    float(trainer.step(x, y).asnumpy())
    m0 = _metrics_mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    tok_s = batch * seq_len * steps / dt
    name = f"gpt2_124m_lm_{dtype}_b{batch}x{seq_len}_train"
    print(json.dumps({
        "metric": name,
        "value": round(tok_s, 1), "unit": "tokens/sec/chip",
        "vs_baseline": _vs_baseline(name, tok_s),
        "step_breakdown": _step_breakdown(m0, dt, steps)}))


def _eager_train_bench(net, x, y, loss_fn, steps: int, batch: int,
                       optimizer: str, opt_params: dict):
    """Shared eager (non-hybridized) training loop: per-op dispatch
    through the lazy bulking engine (MXNET_BULK_MAX_OPS).  Returns
    (wall_dt, metrics_mark_before) with the python dispatch time of
    each step observed into mxnet_step_dispatch_seconds so
    _step_breakdown splits dispatch from the device-execution tail."""
    import time as _time
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, metrics as _metrics

    trainer = mx.gluon.Trainer(net.collect_params(), optimizer,
                               opt_params, kvstore=None)

    def one_step():
        t0 = _time.perf_counter()
        with autograd.record():
            out = net(x)
            loss = loss_fn(out, y).mean()
        loss.backward()
        trainer.step(batch)
        _metrics.STEP_DISPATCH_SECONDS.observe(_time.perf_counter() - t0)
        return loss

    # warmup: segment-cache + per-op compile population (grad buffers
    # materialize on the first step, which changes segment liveness, so
    # two steps are needed before signatures are steady)
    for _ in range(3):
        one_step().asnumpy()

    m0 = _metrics_mark()
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = one_step()
    loss.asnumpy()
    return _time.perf_counter() - t0, m0


def bench_lstm_eager(batch: int, steps: int, dtype: str,
                     seq_len: int) -> None:
    """Config 4 EAGER path (MXNET_BENCH_EAGER=1): the same LSTM LM run
    non-hybridized — per-op imperative dispatch, the workload the lazy
    bulking engine (ISSUE 4) exists for.  step_breakdown.dispatch_s is
    the metric of interest: python dispatch time per step."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import metrics as _metrics

    vocab, embed, hidden = 10000, 650, 650
    mx.random.seed(0)

    class LM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.emb = mx.gluon.nn.Embedding(vocab, embed)
            self.rnn = mx.gluon.rnn.LSTM(hidden, num_layers=2,
                                         layout="NTC")
            self.out = mx.gluon.nn.Dense(vocab, flatten=False)

        def forward(self, x):
            return self.out(self.rnn(self.emb(x)))

    net = LM()
    net.initialize()
    net(mx.np.zeros((2, 8), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    y = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    dt, m0 = _eager_train_bench(net, x, y, loss_fn, steps, batch,
                                "sgd", {"learning_rate": 1.0})
    from mxnet_tpu import bulk
    tok_s = batch * seq_len * steps / dt
    print(json.dumps({
        "metric": f"lstm_ptb_eager_{dtype}_b{batch}x{seq_len}_train",
        "value": round(tok_s, 1), "unit": "tokens/sec/chip",
        "vs_baseline": 0.0, "bulk_max_ops": bulk.max_ops(),
        "step_breakdown": _step_breakdown(m0, dt, steps)}))


def bench_gpt_eager(batch: int, steps: int, dtype: str,
                    seq_len: int) -> None:
    """GPT-2-124M EAGER path (MXNET_BENCH_EAGER=1): non-hybridized
    causal-LM training — per-op dispatch through the bulking engine."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import get_gpt

    vocab = 50257
    mx.random.seed(0)
    net = get_gpt("gpt2_124m", vocab_size=vocab, dropout=0.0,
                  max_length=max(1024, seq_len))
    net.initialize()
    net(mx.np.zeros((2, 16), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    y = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    dt, m0 = _eager_train_bench(net, x, y, loss_fn, steps, batch,
                                "adamw", {"learning_rate": 1e-4})
    from mxnet_tpu import bulk
    tok_s = batch * seq_len * steps / dt
    print(json.dumps({
        "metric": f"gpt2_124m_eager_{dtype}_b{batch}x{seq_len}_train",
        "value": round(tok_s, 1), "unit": "tokens/sec/chip",
        "vs_baseline": 0.0, "bulk_max_ops": bulk.max_ops(),
        "step_breakdown": _step_breakdown(m0, dt, steps)}))


def bench_bulk_smoke() -> None:
    """CI acceptance micro-run (ci/run.sh bulk-smoke, ISSUE 4): a tiny
    eager LSTM LM trained twice — bulked (MXNET_BULK_MAX_OPS=16) vs
    per-op (=1) — asserting

      * >= 1.3x eager->bulked python-dispatch-time reduction,
      * 0 new segment compiles after warmup (steady-state cache), and
      * loss parity within FMA-contraction tolerance (fused segments
        may differ from per-op dispatch in the last ulp — see
        docs/performance.md).
    """
    import time as _time
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, bulk, metrics as _metrics

    vocab, embed, hidden, batch, seq = 120, 16, 16, 4, 6
    steps = int(os.environ.get("MXNET_BENCH_STEPS", "10"))

    def build():
        mx.random.seed(7)

        class LM(mx.gluon.HybridBlock):
            def __init__(self):
                super().__init__()
                self.emb = mx.gluon.nn.Embedding(vocab, embed)
                self.rnn = mx.gluon.rnn.LSTM(hidden, num_layers=1,
                                             layout="NTC")
                self.out = mx.gluon.nn.Dense(vocab, flatten=False)

            def forward(self, x):
                return self.out(self.rnn(self.emb(x)))

        net = LM()
        net.initialize()
        net(mx.np.zeros((2, 3), dtype="int32"))
        return net

    def train(net, n):
        rng = onp.random.RandomState(0)
        x = mx.np.array(rng.randint(0, vocab, (batch, seq))
                        .astype("int32"))
        y = mx.np.array(rng.randint(0, vocab, (batch, seq))
                        .astype("int32"))
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
        trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.5}, kvstore=None)
        losses, t_disp = [], 0.0
        for _ in range(n):
            t0 = _time.perf_counter()
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(batch)
            t_disp += _time.perf_counter() - t0
            losses.append(float(loss.asnumpy()))
        return losses, t_disp

    failures = []

    bulk.set_max_ops(16)
    net = build()
    train(net, 3)                      # warmup: compile the segments
    m0 = _metrics.value("mxnet_bulk_seg_cache_misses_total")
    losses_b, t_bulk = train(net, steps)
    new_compiles = _metrics.value(
        "mxnet_bulk_seg_cache_misses_total") - m0
    if new_compiles != 0:
        failures.append(f"segment cache not steady: {new_compiles:.0f} "
                        f"new compiles after warmup")

    bulk.set_max_ops(1)
    net_e = build()
    train(net_e, 3)
    losses_e, t_eager = train(net_e, steps)
    bulk.set_max_ops(16)

    ratio = t_eager / t_bulk if t_bulk > 0 else float("inf")
    if ratio < 1.3:
        failures.append(f"dispatch reduction {ratio:.2f}x < 1.3x "
                        f"(bulked {t_bulk:.3f}s vs per-op {t_eager:.3f}s)")

    # NOTE: warmup diverges the weights between the two runs only
    # through FMA-level differences, so per-step losses stay comparable
    # at a tight relative tolerance
    max_rel = max(abs(a - b) / max(abs(b), 1e-9)
                  for a, b in zip(losses_b, losses_e))
    if max_rel > 1e-4:
        failures.append(f"loss parity {max_rel:.2e} > 1e-4 "
                        f"(bulked vs per-op)")

    print(json.dumps({
        "metric": "bulk_smoke_lstm_micro",
        "dispatch_reduction_x": round(ratio, 2),
        "bulked_dispatch_s": round(t_bulk, 4),
        "per_op_dispatch_s": round(t_eager, 4),
        "new_compiles_after_warmup": new_compiles,
        "max_loss_rel_diff": float(f"{max_rel:.3e}"),
        "ok": not failures}))
    if failures:
        raise SystemExit("bulk smoke FAILED: " + "; ".join(failures))


def bench_lstm(batch: int, steps: int, dtype: str, seq_len: int) -> None:
    """Config 4: 2-layer LSTM LM (PTB-shape) tokens/sec/chip."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    vocab, embed, hidden = 10000, 650, 650
    mx.random.seed(0)

    class LM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.emb = mx.gluon.nn.Embedding(vocab, embed)
            self.rnn = mx.gluon.rnn.LSTM(hidden, num_layers=2,
                                         layout="NTC")
            self.out = mx.gluon.nn.Dense(vocab, flatten=False)

        def forward(self, x):
            return self.out(self.rnn(self.emb(x)))

    net = LM()
    net.initialize()
    net(mx.np.zeros((2, 8), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(net, lambda o, l: loss_fn(o, l),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 1.0},
                          mesh=mesh, rules=DATA_PARALLEL_RULES)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    y = mx.np.array(rng.randint(0, vocab, (batch, seq_len))
                    .astype("int32"))
    float(trainer.step(x, y).asnumpy())
    float(trainer.step(x, y).asnumpy())
    m0 = _metrics_mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    tok_s = batch * seq_len * steps / dt
    name = f"lstm_ptb_{dtype}_b{batch}x{seq_len}_train"
    print(json.dumps({
        "metric": name,
        "value": round(tok_s, 1), "unit": "tokens/sec/chip",
        "vs_baseline": _vs_baseline(name, tok_s),
        "step_breakdown": _step_breakdown(m0, dt, steps)}))


def bench_vit(batch: int, steps: int, dtype: str, img: int) -> None:
    """Config 9 (beyond-reference): ViT-B/16 training, images/sec/chip —
    the all-matmul vision model that rides the BERT attention path."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import vit_base_patch16
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    mx.random.seed(0)
    net = vit_base_patch16(img_size=img, dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, 3, img, img), dtype="float32"))
    if dtype != "float32":
        net.cast(dtype)

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(net, lambda o, l: loss_fn(o, l),
                          optimizer="adamw",
                          optimizer_params={"learning_rate": 1e-3},
                          mesh=mesh, rules=DATA_PARALLEL_RULES)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randn(batch, 3, img, img).astype(dtype))
    y = mx.np.array(rng.randint(0, 1000, (batch,)).astype("int32"))
    float(trainer.step(x, y).asnumpy())
    float(trainer.step(x, y).asnumpy())
    m0 = _metrics_mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    img_s = batch * steps / dt
    name = f"vit_b16_{dtype}_b{batch}x{img}_train_throughput"
    print(json.dumps({
        "metric": name,
        "value": round(img_s, 1), "unit": "images/sec/chip",
        "vs_baseline": _vs_baseline(name, img_s),
        "step_breakdown": _step_breakdown(m0, dt, steps)}))


def _build_bench_pack(prefix: str, n_images: int, size: int,
                      fmt: str) -> str:
    """Synthetic im2rec-style pack, built once and cached (the bench
    host has no ImageNet; record framing/decode cost is content-
    independent)."""
    import numpy as onp
    from mxnet_tpu import recordio
    rec_path = prefix + ".rec"
    if os.path.exists(rec_path):
        return rec_path
    rs = onp.random.RandomState(0)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", rec_path, "w")
    for i in range(n_images):
        img = rs.randint(0, 256, (size, size, 3)).astype("uint8")
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack_img(
            header, img, quality=90,
            img_fmt=".jpg" if fmt == "jpg" else ".raw"))
    rec.close()
    return rec_path


class _RecordBatcher:
    """The bench's ImageRecordIOParser2 analog: the native C++
    prefetcher (src/recordio.cc) reads record batches ahead on its own
    thread; decode (frombuffer for .raw, PIL for .jpg) + random
    crop/mirror run per image; the batch ships to the device as uint8
    NHWC (4x less host->device traffic than f32) and normalize/transpose/cast
    run in-graph on the chip."""

    def __init__(self, rec_path: str, batch: int, img: int,
                 pack_size: int = 256) -> None:
        import numpy as onp
        from mxnet_tpu._native import NativePrefetcher
        from mxnet_tpu import recordio
        if img > pack_size:
            raise ValueError(
                f"MXNET_BENCH_IMAGE={img} exceeds the packed image size "
                f"{pack_size} — the random crop needs source images at "
                "least as large as the crop")
        self._unpack = recordio.unpack_img
        self._pf = NativePrefetcher(rec_path, batch, capacity=8)
        self._batch, self._img = batch, img
        self._pack_size = pack_size
        self._rng = onp.random.RandomState(7)
        self._onp = onp

    def next(self):
        onp = self._onp
        recs = self._pf.next_batch()
        if len(recs) < self._batch:          # epoch end: wrap around
            self._pf.reset()
            recs = self._pf.next_batch()
        if len(recs) < self._batch:
            raise RuntimeError(
                f"record pack holds fewer than one batch "
                f"({len(recs)} < {self._batch}) — raise "
                "MXNET_BENCH_RECORD_N or lower MXNET_BENCH_BATCH")
        B, S = self._batch, self._img
        out = onp.empty((B, S, S, 3), "uint8")
        labels = onp.empty((B,), "int32")
        ys = self._rng.randint(0, self._pack_size + 1 - S, size=B)
        xs = self._rng.randint(0, self._pack_size + 1 - S, size=B)
        flips = self._rng.rand(B) < 0.5
        for i, r in enumerate(recs):
            hdr, arr = self._unpack(r)
            a = arr[ys[i]:ys[i] + S, xs[i]:xs[i] + S]
            out[i] = a[:, ::-1] if flips[i] else a
            labels[i] = int(hdr.label)
        return out, labels

    def close(self):
        self._pf.close()


def bench_resnet_recordio(batch: int, steps: int, dtype: str, img: int,
                          model_name: str) -> None:
    """Config 2 with REAL data IO (VERDICT r3 missing 1): the recordio
    pack feeds training through prefetch + decode + augment + H2D, and
    the number reported is the sustained end-to-end rate."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision as zoo
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    fmt = os.environ.get("MXNET_BENCH_RECORD_FMT", "raw")
    n_rec = int(os.environ.get("MXNET_BENCH_RECORD_N", "512"))
    # pack images sized to the requested crop (+32 jitter margin) so
    # MXNET_BENCH_IMAGE > 224 works; size in the cache name keeps packs
    # of different sizes from colliding
    pack_size = max(256, img + 32)
    pack = _build_bench_pack(f"/tmp/mxtpu_bench_{fmt}_{n_rec}_{pack_size}",
                             n_rec, pack_size, fmt)

    mx.random.seed(0)
    inner = zoo.get_model(model_name, classes=1000)

    class UInt8Net(mx.gluon.HybridBlock):
        """Normalize/NCHW/cast on-device: the host ships raw uint8.
        ``_feed_dtype`` tracks the inner net's parameter dtype (f32 at
        settle time, the bench dtype after cast)."""

        def __init__(self):
            super().__init__()
            self.net = inner
            self._feed_dtype = "float32"

        def forward(self, x):
            x = x.astype("float32") * (1.0 / 127.5) - 1.0
            x = x.transpose(0, 3, 1, 2).astype(self._feed_dtype)
            return self.net(x)

    net = UInt8Net()
    net.initialize()
    # spatial-dependent heads (VGG Flatten+Dense, Inception's fixed
    # AvgPool) must settle deferred shapes at the REAL image size; the
    # fully-convolutional families use a small fast settle (same rule
    # as the synthetic path)
    fully_conv = model_name.startswith(
        ("resnet", "mobilenet", "squeezenet", "densenet"))
    settle = 64 if fully_conv else img
    net(mx.np.zeros((1, settle, settle, 3), dtype="uint8"))
    if dtype != "float32":
        inner.cast(dtype)
        net._feed_dtype = dtype

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, rules=DATA_PARALLEL_RULES)

    loader = _RecordBatcher(pack, batch, img, pack_size=pack_size)

    # loader-only rate (decode+augment, no device work) — the IO bound
    t0 = time.perf_counter()
    lsteps = max(5, min(10, steps // 4))
    for _ in range(lsteps):
        loader.next()
    loader_img_s = batch * lsteps / (time.perf_counter() - t0)

    x_np, y_np = loader.next()
    float(trainer.step(mx.np.array(x_np),
                       mx.np.array(y_np)).asnumpy())
    float(trainer.step(mx.np.array(x_np),
                       mx.np.array(y_np)).asnumpy())

    # timed end-to-end through the PRODUCTION input pipeline (ISSUE 9):
    # a DevicePrefetcher runs decode + augment + the SHARDED device
    # commit of batch k+1 on its background thread while step k
    # executes — batches arrive at the step already mesh-resident
    # (trainer placement attached), so the step loop's only input work
    # is a queue pop.
    from mxnet_tpu import metrics as _metrics
    from mxnet_tpu.io import DevicePrefetcher

    def _batches():
        while True:
            yield loader.next()

    pf = DevicePrefetcher(_batches(), depth=4).attach(trainer)
    it = iter(pf)
    cur = next(it)
    m0 = _metrics_mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        td = time.perf_counter()
        nxt = next(it)                 # device-resident batch k+1
        # the trainer can't see this wait (it receives device-resident
        # arrays), so account the input stall as data here — without it
        # the breakdown folds loader stalls into sync_s
        _metrics.STEP_DATA_SECONDS.observe(time.perf_counter() - td)
        loss = trainer.step(*cur)      # ... batch k+2 fetches under it
        cur = nxt
    loss.asnumpy()
    dt = time.perf_counter() - t0
    it.close()       # stop the epoch producer before the loader goes away
    pf.close()
    loader.close()

    img_per_sec = batch * steps / dt
    print(json.dumps({
        "metric": f"{model_name}_{dtype}_b{batch}_recordio_{fmt}"
                  "_train_throughput",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_S, 3),
        "loader_img_s": round(loader_img_s, 1),
        "step_breakdown": _step_breakdown(m0, dt, steps),
    }))


def run_all_configs() -> None:
    """Default driver mode (VERDICT r4 directive 5): one invocation
    emits all five headline configs — bert, gpt, lstm, vit (r5), then
    resnet50 LAST so the driver's last-line parse keeps the metric of
    record continuous with prior rounds.  Each model runs in its own
    subprocess: the chip's HBM and the compile cache reset between
    models, so no config inherits the previous one's memory pressure."""
    import subprocess
    failures = []
    for model in ["bert", "gpt", "lstm", "vit", "gen", "resnet50_v1"]:
        env = dict(os.environ, MXNET_BENCH_MODEL=model)
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                              env=env, capture_output=True, text=True)
        # a config may emit SEVERAL metric lines (gen: tokens/sec +
        # TTFT); forward each, in order — resnet50 stays the last
        # config, so the driver's last-line parse is unchanged
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith('{"metric"')]
        if proc.returncode != 0 or not lines:
            failures.append(model)
            sys.stderr.write(f"[bench] {model} FAILED rc={proc.returncode}\n"
                             f"{proc.stderr[-2000:]}\n")
            continue
        for line in lines:
            print(line, flush=True)
    if failures:
        raise SystemExit(f"bench configs failed: {failures}")


def main() -> None:
    if "--check" in sys.argv:
        i = sys.argv.index("--check")
        return bench_check([p for p in sys.argv[i + 1:]
                            if not p.startswith("-")])
    # defaults = the headline config: ResNet-50 bf16 b128 training —
    # bf16 is the TPU-native training dtype
    batch = int(os.environ.get("MXNET_BENCH_BATCH", "128"))
    steps = int(os.environ.get("MXNET_BENCH_STEPS", "40"))
    model_name = os.environ.get("MXNET_BENCH_MODEL", "")
    if not model_name:
        # one process per chip: this parent must not have touched jax
        # when the per-model children start
        return run_all_configs()
    import numpy as onp
    import jax
    dtype = os.environ.get("MXNET_BENCH_DTYPE", "bfloat16")
    img = int(os.environ.get("MXNET_BENCH_IMAGE", "224"))

    if model_name == "bulk_smoke":
        return bench_bulk_smoke()
    if model_name == "gen":
        return bench_gen_serving()
    if model_name == "dist_comm":
        return bench_dist_comm()
    eager = os.environ.get("MXNET_BENCH_EAGER", "0") == "1"
    if eager and model_name.startswith("lstm"):
        if "MXNET_BENCH_BATCH" not in os.environ:
            batch = 20   # eager dispatch-bound: a smaller batch keeps
            #              the per-step python op count the bottleneck
        return bench_lstm_eager(batch, steps, dtype,
                                int(os.environ.get("MXNET_BENCH_SEQLEN",
                                                   "35")))
    if eager and model_name.startswith("gpt"):
        if "MXNET_BENCH_BATCH" not in os.environ:
            batch = 4
        return bench_gpt_eager(batch, steps, dtype,
                               int(os.environ.get("MXNET_BENCH_SEQLEN",
                                                  "256")))
    if model_name.startswith("bert"):
        if os.environ.get("MXNET_BENCH_BERT_ARCH", "base") == "large" \
                and "MXNET_BENCH_BATCH" not in os.environ:
            batch = 16   # measured best fit (BASELINE row 3c); b48 is
            #              ~base-b128-equivalent and OOMs
        elif "MXNET_BENCH_BATCH" not in os.environ:
            # measured best config (BASELINE 3, r4): b48 runs 143.9k
            # tok/s; the old b128 default OOMs in the r4 terminal env
            # (90 MB over; r3's own commit reproduces the OOM)
            batch = 48
        return bench_bert(batch, steps, dtype,
                          int(os.environ.get("MXNET_BENCH_SEQLEN", "512")))
    if model_name.startswith("gpt"):
        if "MXNET_BENCH_BATCH" not in os.environ:
            batch = 8            # BASELINE config 6 (b128 at T=1024
            #                      wants 63G HBM — not a gpt config)
        return bench_gpt(batch, steps, dtype,
                         int(os.environ.get("MXNET_BENCH_SEQLEN", "1024")))
    if model_name.startswith("lstm"):
        return bench_lstm(batch, steps, dtype,
                          int(os.environ.get("MXNET_BENCH_SEQLEN", "35")))
    if model_name.startswith("vit"):
        return bench_vit(batch, steps, dtype, img)
    if os.environ.get("MXNET_BENCH_DATA", "synthetic") == "recordio":
        return bench_resnet_recordio(batch, steps, dtype, img, model_name)

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision as zoo
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh, \
        DATA_PARALLEL_RULES

    mx.random.seed(0)
    net = zoo.get_model(model_name, classes=1000)
    net.initialize()

    x_np = onp.random.uniform(-1, 1, (batch, 3, img, img)).astype(dtype)
    y_np = onp.random.randint(0, 1000, (batch,)).astype("int32")
    # settle deferred shapes once (eagerly, off the clock), THEN cast —
    # casting first would leave late-initialized params in float32.
    # Fully-convolutional families (global-pool head) get a small settle
    # size for a faster eager warmup; spatial-dependent heads (VGG
    # Flatten+Dense, Inception's fixed AvgPool) must settle at the real
    # image size.
    fully_conv = model_name.startswith(
        ("resnet", "mobilenet", "squeezenet", "densenet"))
    settle = 64 if fully_conv else img
    net(mx.np.zeros((1, 3, settle, settle), dtype="float32"))
    if dtype != "float32":
        net.cast(dtype)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, rules=DATA_PARALLEL_RULES)

    x, y = mx.np.array(x_np), mx.np.array(y_np)
    # two warmup steps: the first compiles; the second recompiles with the
    # donated buffers' optimized on-device layouts (one-time, off the clock)
    float(trainer.step(x, y).asnumpy())
    float(trainer.step(x, y).asnumpy())

    # timed: pipelined async step dispatches, one sync at the end.
    # (A fused lax.scan variant — trainer.run_steps — measured SLOWER
    # here: holding `steps` input batches on-device raises HBM pressure.)
    m0 = _metrics_mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0

    img_per_sec = batch * steps / dt
    print(json.dumps({
        "metric": f"{model_name}_{dtype}_b{batch}_train_throughput",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_S, 3),
        "step_breakdown": _step_breakdown(m0, dt, steps),
    }))


if __name__ == "__main__":
    main()
