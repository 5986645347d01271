"""Overlapped-collectives acceptance smoke (ci/run.sh dist-comm-smoke,
in tier-1).

Bounded (~60s) proof of the ISSUE-14 contract on a CALIBRATED
SYNTHETIC-SLOW WIRE (``MXNET_KV_SYNTH_WIRE_GBPS``: every kvstore push
blocks until its payload is materialized — what any real wire must do —
then charges raw_bytes/rate of transmission time):

1. **overlap**: with the bucketed comm-thread scheduler on
   (``MXNET_KV_OVERLAP=1``, the default), steps/sec reaches >= 1.3x
   the serialized push-all/pull-all path on a wire calibrated so comm
   time ~ per-step compute — step time approaches max(compute, comm)
   instead of their sum.  The workload is update-heavy (16 adam
   parameters of 4 MB, a cheap scalar loss) because the optimizer
   update is exactly the compute the per-bucket wait frees the
   schedule to hide wire under.  Wall clocks take the min of two runs
   per leg (this rig's host-load swings are +/-25-40%), and the whole
   wire calibration gets one retry on a miss; the deterministic gates
   below are never retried.
2. **losses bit-identical** for the lossless ctype (none): the
   overlapped run's per-step losses equal the serialized run's exactly
   — only the schedule moved, never the math.
3. **replay-identical for 2bit**: two overlapped runs under 2-bit
   error-feedback compression produce bit-identical loss sequences —
   bucket composition is fixed by registration order, so the per-key
   residuals are deterministic under scheduling.
4. **steady state**: 0 XLA compiles after warmup across the timed
   overlapped windows.

Plus the ISSUE-15 **backward-overlap leg**: with bulking ON and
per-layer backward segmentation (``MXNET_BULK_BACKWARD_SEGMENTS=
param``) + the event-driven streaming enqueue
(``MXNET_KV_BACKWARD_STREAM=1``), a backward-heavy chain workload on
the same calibrated slow wire must reach

5. **>= 1.5x** steps/sec vs the serialized path, AND **strictly
   faster** than PR-14's optimizer-only overlap (segments off, stream
   off) on the identical wire — the proof that buckets now hide under
   backward itself;
6. **losses bit-identical** serialized-vs-streamed (same segmentation
   both legs: only the schedule moved);
7. **0 XLA compiles after warmup** in the streamed timed windows
   (per-layer segments are steady-state cache hits, not per-step
   recompiles);
8. **warm restart**: the same streamed workload run twice as fresh
   processes sharing jax's persistent compilation cache
   (``JAX_COMPILATION_CACHE_DIR``) produces bit-identical losses, and
   the restarted process still reports 0 steady-state compiles after
   its warmup.

Exit code 0 = all assertions held.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_PARAMS = 16
PARAM_ELEMS = 1024 * 1024            # 4 MB f32 each
BUCKET_BYTES = 8 * 1024 * 1024       # 2 params per bucket -> 8 buckets
STEPS = 6
WARM = 3

# backward-overlap leg: an embedding-shaped chain — each layer matmuls
# through a [:BWD_W] slice of a (BWD_ROWS, BWD_W) parameter, so
# forward is a small matmul while backward (d_param scatter + d_h) and
# the adam sweep scale with the full 1.5 MB parameter.  Measured split
# on this rig: fwd ~11%, bwd ~23%, upd ~66% of the compute step — the
# wire is calibrated to ~bwd+upd, which optimizer-only overlap cannot
# hide (wire > upd) but streaming during backward can.
BWD_PARAMS = 16
BWD_W = 256
BWD_ROWS = 1536                      # param (1536, 256) = 1.5 MB f32
BWD_BUCKET = 2 * BWD_ROWS * BWD_W * 4   # 2 params/bucket -> 6 buckets
# wire ~= 0.75x the compute step: just fills the post-forward window
# (bwd+upd), so streaming can sink nearly all of it under compute
# while optimizer-only overlap (wire > upd) cannot
BWD_WIRE_FRAC = 0.75
BWD_STEPS = 4
BWD_WARM = 3


def _params(seed=0):
    import mxnet_tpu as mx
    mx.random.seed(seed)
    ps = {}
    for j in range(N_PARAMS):
        p = mx.gluon.Parameter(f"w{j}", shape=(PARAM_ELEMS,))
        p.initialize()
        ps[f"w{j}"] = p
    return ps


def _run(steps=STEPS, compression=None, seed=0):
    """One fresh training leg; returns (timed wall seconds, per-step
    loss bytes).  The loss reads a tiny slice of every parameter, so
    backward is cheap while the adam update sweeps the full 64 MB —
    the update-dominated regime the scheduler hides wire under."""
    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.ndarray import ops
    ps = _params(seed)
    tr = mx.gluon.Trainer(ps, "adam", {"learning_rate": 1e-3},
                          compression_params=compression)
    losses = []
    t0 = c0 = None
    for s in range(WARM + steps):
        if s == WARM:
            # warmup compiled this fresh trainer's programs; the timed
            # window must see none
            mx.waitall()
            c0 = metrics.value("mxnet_compile_misses_total")
            t0 = time.perf_counter()
        with mx.autograd.record():
            loss = ops.add_n(
                *[p.data()[:256] for p in ps.values()]).mean()
        loss.backward()
        tr.step(1)
        if s >= WARM:
            losses.append(loss.asnumpy().tobytes())
    mx.waitall()
    wall = time.perf_counter() - t0
    return wall, losses,         metrics.value("mxnet_compile_misses_total") - c0


def _run_bwd(steps=BWD_STEPS, seed=0, n_params=BWD_PARAMS,
             rows=BWD_ROWS, width=BWD_W, batch=64, warm=BWD_WARM):
    """One fresh backward-heavy training leg (the sliced-matmul
    chain): every layer's gradient is produced by its own pullback, so
    with segmentation + streaming the wire starts while later layers
    are still differentiating.  Returns (timed wall seconds, per-step
    loss bytes, compiles after warmup)."""
    import mxnet_tpu as mx
    from mxnet_tpu import bulk, metrics
    from mxnet_tpu.ndarray import ops
    bulk.reset_caches()
    mx.random.seed(seed)
    ps = {}
    for j in range(n_params):
        p = mx.gluon.Parameter(f"b{j}", shape=(rows, width))
        p.initialize()
        ps[f"b{j}"] = p
    tr = mx.gluon.Trainer(ps, "adam", {"learning_rate": 1e-4})
    x = mx.np.ones((batch, width))
    losses = []
    t0 = c0 = None
    for s in range(warm + steps):
        if s == warm:
            mx.waitall()
            c0 = metrics.value("mxnet_compile_misses_total")
            t0 = time.perf_counter()
        with mx.autograd.record():
            h = x
            for p in ps.values():
                h = ops.tanh(ops.dot(h, p.data()[:width]))
            loss = h.mean()
        loss.backward()
        tr.step(1)
        if s >= warm:
            losses.append(loss.asnumpy().tobytes())
    mx.waitall()
    wall = time.perf_counter() - t0
    return wall, losses, \
        metrics.value("mxnet_compile_misses_total") - c0


# every env knob the backward legs mutate — saved and restored
# symmetrically so main()'s later legs see no leakage
_LEG_ENV_KEYS = ("MXNET_KV_OVERLAP", "MXNET_BULK_BACKWARD_SEGMENTS",
                 "MXNET_KV_BACKWARD_STREAM", "MXNET_KV_SYNTH_WIRE_GBPS",
                 "MXNET_KV_BUCKET_BYTES")


def _restore_env(saved) -> None:
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _bwd_env(overlap, segments, stream, gbps):
    os.environ["MXNET_KV_OVERLAP"] = overlap
    os.environ["MXNET_BULK_BACKWARD_SEGMENTS"] = segments
    os.environ["MXNET_KV_BACKWARD_STREAM"] = stream
    os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = gbps
    os.environ["MXNET_KV_BUCKET_BYTES"] = str(BWD_BUCKET)


def backward_leg(failures) -> dict:
    """Legs 5-7: serialized vs optimizer-only overlap vs streamed-
    during-backward, all on one calibrated slow wire.  Env knobs the
    legs flip are restored on return."""
    saved = {k: os.environ.get(k) for k in _LEG_ENV_KEYS}
    try:
        return _backward_leg_inner(failures)
    finally:
        _restore_env(saved)


def _backward_leg_inner(failures) -> dict:
    from mxnet_tpu import metrics
    push_bytes = BWD_PARAMS * BWD_ROWS * BWD_W * 4
    rep = {}
    best = None
    for attempt in range(3):
        # calibrate the wire to ~BWD_WIRE_FRAC of the compute-only
        # step (~ the bwd+upd share: too long for optimizer-only
        # overlap to hide, short enough to vanish under bwd+upd)
        _bwd_env("0", "param", "0", "0")
        t_nowire, _, _ = _run_bwd()
        step_s = max(t_nowire / BWD_STEPS, 0.004)
        gbps = f"{push_bytes / (BWD_WIRE_FRAC * step_s * 1e9):.9f}"
        rep["wire_ms"] = BWD_WIRE_FRAC * step_s * 1e3

        _bwd_env("0", "param", "0", gbps)
        s1, losses_serial, _ = _run_bwd()
        s2, _, _ = _run_bwd()
        serial_s = min(s1, s2)

        # PR-14 baseline: overlap on, but one fused backward and no
        # event path — the wire can only hide under the adam sweep.
        # min-of-3 on both overlapped legs: their strict comparison is
        # the tightest gate, and one lucky/unlucky run must not decide
        # it on a rig with ±25-40% load swings
        _bwd_env("1", "off", "0", gbps)
        opt_s = min(_run_bwd()[0] for _ in range(3))

        # ISSUE-15: per-layer segments stream buckets during backward
        # (delta, not cumulative: earlier legs also stream by default)
        enq0 = metrics.value("mxnet_kv_stream_enqueues_total")
        _bwd_env("1", "param", "1", gbps)
        b1, losses_bwd, comp1 = _run_bwd()
        b2, _, comp2 = _run_bwd()
        b3, _, comp3 = _run_bwd()
        bwd_s = min(b1, b2, b3)

        rep.update(
            serial_s=serial_s, opt_s=opt_s, bwd_s=bwd_s,
            ratio=serial_s / bwd_s if bwd_s > 0 else float("inf"),
            opt_ratio=serial_s / opt_s if opt_s > 0 else float("inf"),
            compiles=comp1 + comp2 + comp3,
            stream_enqueues=metrics.value(
                "mxnet_kv_stream_enqueues_total") - enq0)
        ok = rep["ratio"] >= 1.5 and bwd_s < opt_s
        if best is None or (ok, rep["ratio"]) > \
                (best["_ok"], best["ratio"]):
            best = dict(rep)
            best["_ok"] = ok
            best["_losses"] = (losses_serial, losses_bwd)
        if ok:
            break
        print(f"backward-leg attempt {attempt}: {rep['ratio']:.2f}x "
              f"(want >=1.5x), streamed {bwd_s:.2f}s vs opt-only "
              f"{opt_s:.2f}s — recalibrating (host-load noise on this "
              "rig is ±25-40%)", flush=True)
    rep = best
    losses_serial, losses_bwd = rep.pop("_losses")
    rep.pop("_ok", None)
    if rep["ratio"] < 1.5:
        failures.append(
            f"backward-overlap speedup {rep['ratio']:.2f}x < 1.5x vs "
            f"serialized (serial {rep['serial_s']:.2f}s, streamed "
            f"{rep['bwd_s']:.2f}s)")
    if rep["bwd_s"] >= rep["opt_s"]:
        failures.append(
            f"streamed-during-backward ({rep['bwd_s']:.2f}s) not "
            f"faster than optimizer-only overlap ({rep['opt_s']:.2f}s) "
            "on the same wire")
    if losses_serial != losses_bwd:
        failures.append("streamed losses diverged from serialized "
                        "(same segmentation: must be bit-identical)")
    if rep["compiles"] != 0:
        failures.append(f"{rep['compiles']:.0f} XLA compiles after "
                        "warmup in the streamed windows (want 0)")
    if rep["stream_enqueues"] <= 0:
        failures.append("no bucket was event-enqueued during backward "
                        "(the streaming path never engaged)")
    os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "0"
    return rep


def restart_leg(failures) -> dict:
    """Leg 8: two fresh processes share jax's persistent compilation
    cache (JAX_COMPILATION_CACHE_DIR); the restarted one must replay
    bit-identical losses with 0 steady-state compiles after its
    warmup, and compile no more in its warmup than the cold one (what
    it loads instead is tests/test_warm_restart.py's gate).  The
    counts are returned for visibility."""
    reports = []
    with tempfile.TemporaryDirectory(prefix="dist-comm-cache-") as d:
        for _ in range(2):
            env = dict(os.environ,
                       JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=os.path.join(d, "cc"),
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--restart-child"],
                capture_output=True, text=True, timeout=240, env=env)
            if out.returncode != 0:
                failures.append("warm-restart child failed: "
                                + out.stderr[-500:])
                return {}
            reports.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warmr = reports
    if cold["losses"] != warmr["losses"]:
        failures.append("warm-restarted losses diverged from the cold "
                        "run (must be bit-identical)")
    if warmr["compiles_after_warmup"] != 0:
        failures.append(
            f"{warmr['compiles_after_warmup']:.0f} steady-state XLA "
            "compiles in the warm-restarted process (want 0)")
    # a restarted warmup must never compile MORE than the cold one did
    # (segmentation is deterministic, so the program set is identical)
    if warmr["warmup_compiles"] > cold["warmup_compiles"]:
        failures.append(
            f"warm restart compiled more than the cold boot "
            f"({warmr['warmup_compiles']:.0f} vs "
            f"{cold['warmup_compiles']:.0f} warmup compiles — the "
            "per-layer segment grid is not restart-deterministic)")
    return {"cold_warmup_compiles": cold["warmup_compiles"],
            "warm_warmup_compiles": warmr["warmup_compiles"],
            "restart_ok": True}


def _restart_child() -> None:
    """Subprocess body for the warm-restart leg: a small streamed run,
    fast wire (this leg gates determinism + compiles, not timing)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    _bwd_env("1", "param", "1", "10000")
    os.environ["MXNET_KV_BUCKET_BYTES"] = str(128 * 1024)
    wall, losses, compiles = _run_bwd(steps=3, n_params=6, rows=256,
                                      width=128, batch=32)
    from mxnet_tpu import metrics
    total = metrics.value("mxnet_compile_misses_total")
    print(json.dumps({
        "losses": [lo.hex() for lo in losses],
        "compiles_after_warmup": compiles,
        "warmup_compiles": total - compiles,   # process boot -> warmup
        "wall_s": wall,
    }), flush=True)


def main() -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu import metrics

    os.environ["MXNET_KV_BUCKET_BYTES"] = str(BUCKET_BYTES)
    push_bytes = N_PARAMS * PARAM_ELEMS * 4

    failures = []
    ratio = serial_s = overlap_s = wire_ms = 0.0
    compiles = 0.0
    for attempt in range(2):
        # -- calibrate the wire to ~0.8x the compute-only step: comm
        # comparable to compute, the regime the scheduler exists for ---
        os.environ["MXNET_KV_OVERLAP"] = "0"
        os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "0"
        t_nowire, _, _ = _run()
        step_s = max(t_nowire / STEPS, 0.004)
        wire_ms = 0.8 * step_s * 1e3
        os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = \
            f"{push_bytes / (0.8 * step_s * 1e9):.9f}"

        # -- serialized vs overlapped on the same slow wire (min of 2) ------
        s1, losses_serial, _ = _run()
        s2, _, _ = _run()
        serial_s = min(s1, s2)
        os.environ["MXNET_KV_OVERLAP"] = "1"
        o1, losses_overlap, comp1 = _run()
        o2, _, comp2 = _run()
        compiles = comp1 + comp2
        overlap_s = min(o1, o2)
        ratio = serial_s / overlap_s if overlap_s > 0 else float("inf")
        if ratio >= 1.3:
            break
        print(f"attempt {attempt}: ratio {ratio:.2f}x < 1.3x "
              f"(serial {serial_s:.2f}s, overlapped {overlap_s:.2f}s) "
              "— recalibrating once", flush=True)
    if ratio < 1.3:
        failures.append(
            f"overlapped speedup {ratio:.2f}x < 1.3x on the calibrated "
            f"slow wire (serial {serial_s:.2f}s vs overlapped "
            f"{overlap_s:.2f}s for {STEPS} steps)")

    # losses bit-identical: same seed, same math — only scheduling moved
    if losses_serial != losses_overlap:
        failures.append("overlapped losses diverged from serialized "
                        "(lossless ctype must be bit-identical)")

    # deterministic gate: steady-state compiles across the overlapped
    # timed windows (the two legs share every program shape)
    if compiles != 0:
        failures.append(f"{compiles:.0f} XLA compiles after warmup in "
                        "the overlapped windows (want 0)")

    # 2bit error-feedback replay determinism under scheduling
    _, l2a, _ = _run(steps=4, compression={"type": "2bit",
                                           "threshold": 1e-4}, seed=1)
    _, l2b, _ = _run(steps=4, compression={"type": "2bit",
                                           "threshold": 1e-4}, seed=1)
    if l2a != l2b:
        failures.append("2bit overlapped replay diverged (per-key "
                        "residuals must be deterministic under the "
                        "scheduler)")

    os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "0"
    overlap_frac = metrics.value("mxnet_kv_overlap_fraction")
    buckets = metrics.value("mxnet_kv_buckets_total")
    print(f"dist-comm-smoke: {ratio:.2f}x steps/sec overlapped vs "
          f"serialized (wire {wire_ms:.0f}ms/step, {buckets:.0f} "
          f"buckets total, last-round overlap fraction "
          f"{overlap_frac:.2f}), loss parity bit-exact, 2bit replay "
          f"identical, {compiles:.0f} compiles after warmup")

    # -- ISSUE-15 legs: overlap during backward itself ------------------
    bwd = backward_leg(failures)
    print(f"backward-overlap leg: {bwd.get('ratio', 0):.2f}x vs "
          f"serialized (optimizer-only {bwd.get('opt_ratio', 0):.2f}x; "
          f"streamed {bwd.get('bwd_s', 0):.2f}s < opt-only "
          f"{bwd.get('opt_s', 0):.2f}s), wire "
          f"{bwd.get('wire_ms', 0):.0f}ms/step, "
          f"{bwd.get('stream_enqueues', 0):.0f} buckets event-enqueued "
          f"during backward, "
          f"{bwd.get('compiles', 0):.0f} compiles after warmup")
    rst = restart_leg(failures)
    if rst.get("restart_ok"):
        print("warm-restart leg: losses bit-identical across restart, "
              "0 steady-state compiles in the restarted process")
    if failures:
        raise SystemExit("dist-comm-smoke FAILED: " + "; ".join(failures))


if __name__ == "__main__":
    if "--restart-child" in sys.argv:
        _restart_child()
    else:
        main()
