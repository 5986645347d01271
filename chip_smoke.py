"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user would
call, at the full width of ``gpt2_124m`` (12 L, 768 wide, 12 heads,
vocab 50257; random weights from a seed):

* **train** — ``get_gpt`` -> ``net.cast("bfloat16")`` -> ``SPMDTrainer``
  (adamw, ``make_mesh({"dp": 1})``) on b8 x 1024 synthetic tokens: two
  warm-up steps, then timed steps.  Checks a finite, falling loss, every
  parameter and optimizer state on a TPU device, the Mosaic custom call
  inside the compiled step, and the flash kernel against
  ``jax.nn.dot_product_attention`` on the chip.
* **train4** — the same on ``make_mesh({"dp": 2, "tp": 2})`` with
  ``DEFAULT_TRANSFORMER_RULES`` when jax reports >= 4 TPU devices
  (``skipped: <n> device(s)`` otherwise): tp-sharded parameters on 4
  distinct devices, the kernel in the step, first-step loss equal to the
  1-chip run's within a bf16 tolerance.
* **decode** — the serving engine as ``tools/serve.py`` builds it
  (``DecodeModel.from_block`` -> ``GenerationEngine``), warmed up, then
  the compiled decode step at the engine's top KV bucket: its optimized
  HLO may hold no ``copy`` or ``transpose`` of a whole K or V buffer
  (the per-token relayout PERF.md's PR 27 entry removed).
* **serve** — ``tools/serve.py --generate --zoo-gpt gpt2_124m --port 0``
  as a child: streamed concurrent ``/v1/generate`` requests across
  prompt buckets and a KV-bucket growth, contiguous indexes, a done
  frame, identical tokens for the same greedy request sent twice, no
  compile between the first and last request, exit 0 on SIGTERM.

One process per chip: this parent imports neither ``jax`` nor
``mxnet_tpu``; each phase is a child that owns the chip and has exited
before the next one starts.  There is no rehearsal mode: a child that
finds no TPU exits non-zero naming what jax found, and so does this
script, printing no result.  Run it through the chip tool:

    chiprun -- python chip_smoke.py

The last stdout line is ``{"ok": true, "device": {...}}``; the lines
before it are one JSON result per phase (platform, device kind and
count, jax version, whether libmxtpu.so loaded, warm-up seconds and
programs compiled, apart from step / request seconds).
"""
import argparse
import http.client
import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

MODEL = "gpt2_124m"
VOCAB = 50257
BATCH, SEQ = 8, 1024
WARMUP_STEPS, TIMED_STEPS = 2, 4
LEARNING_RATE = 1e-4
# flash kernel vs jax.nn.dot_product_attention, both bf16 on the chip,
# forward and each gradient: max |a - b| over max |b|.  bf16 carries 8
# mantissa bits (2^-8 = 0.4 % per rounding) and the two paths round the
# probabilities and outputs at different points; the v5e measured
# 0.33-0.45 % (PR 21), so 2 % flags a wrong kernel, not a rounding.
KERNEL_TOL = 0.02
# first-step loss, 4 chips vs 1 chip, same seed and batch: the tp split
# changes the order of the bf16 partial sums in every row-parallel
# matmul (12 layers x 2), nothing else.  The loss itself is a bf16 value
# (steps of 0.0625 near 11.5, i.e. 0.5 %); the v5e measured 0 (PR 21)
LOSS_MATCH_TOL = 0.02
# prompts in four different prompt buckets (8, 32, 128, 512); the third
# outgrows the smallest KV bucket (128) while decoding, the fourth is
# admitted above it
PROMPT_LENGTHS, NEW_TOKENS = [5, 20, 100, 300], [24, 24, 48, 24]
BUDGET_S = 1150.0           # the contract allows 1200 s, compilation included


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# children: each owns the chip for its lifetime
# ---------------------------------------------------------------------------

def _require_tpu():
    """The device as jax reports it; exits non-zero, naming what jax
    found, unless that is a TPU."""
    import jax
    devs = jax.devices()
    found = sorted({d.platform for d in devs})
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but jax {jax.__version__} "
                 f"reports {len(devs)} device(s) of platform {found} "
                 f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    from mxnet_tpu.runtime import device_info
    return device_info()


def _on_tpu(tree, what):
    import jax
    for a in jax.tree_util.tree_leaves(tree):
        if isinstance(a, jax.Array):
            bad = [d for d in a.devices() if d.platform != "tpu"]
            check(not bad, f"{what}: buffer of shape {a.shape} lives on "
                           f"{bad}, not on a TPU device")


def _kernel_parity():
    """flash_attention vs jax.nn.dot_product_attention on the chip at
    GPT-2's attention shape, forward and backward, plus one dropout
    call (the on-chip PRNG path has no CPU implementation to test)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.ops.pallas.attention import flash_attention

    rng = onp.random.RandomState(1)
    q, k, v, g = (jnp.asarray(rng.randn(2, SEQ, 12, 64), jnp.bfloat16)
                  for _ in range(4))

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(g)

    got = jax.jit(lambda: run(
        lambda q, k, v: flash_attention(q, k, v, causal=True)))()
    ref = jax.jit(lambda: run(
        lambda q, k, v: jax.nn.dot_product_attention(
            q, k, v, is_causal=True)))()
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        check(bool(jnp.isfinite(a).all()), f"flash {name} not finite")
        errs[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        check(errs[name] <= KERNEL_TOL,
              f"flash {name} off dense by {errs[name]:.4f} "
              f"(> {KERNEL_TOL})")
    dropped = jax.jit(lambda: flash_attention(
        q, k, v, causal=True, dropout=0.1,
        dropout_seed=jnp.asarray([3, 7], jnp.int32)))()
    check(bool(jnp.isfinite(dropped.astype(jnp.float32)).all()),
          "flash with dropout not finite")
    check(float(jnp.abs(dropped.astype(jnp.float32)
                        - got[0].astype(jnp.float32)).max()) > 0.0,
          "flash with dropout=0.1 equals the undropped output")
    return {k: round(e, 5) for k, e in errs.items()}


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* (copy|transpose)\(")


def cache_sized_relayouts(hlo_text, n_elements):
    """The ``copy`` / ``transpose`` instructions of an optimized HLO
    module whose result has ``n_elements`` elements (one whole K or V
    buffer), fused computations included.  ``copy-start`` / ``copy-done``
    (a move between memory spaces, same layout) do not count."""
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        if math.prod(map(int, filter(None, m.group(1).split(",")))) \
                == n_elements:
            found.append(line.strip()[:200])
    return found


def child_decode():
    t_proc = time.perf_counter()
    device = _require_tpu()
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo.gpt import get_gpt

    net = get_gpt(MODEL, dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 4), dtype="int32"))
    model = serving.DecodeModel.from_block(net)
    engine = serving.GenerationEngine(model)
    warmed = engine.warmup()
    cache = engine.cache
    cache.grow(cache.grid[-1])
    S = cache.max_slots
    zeros = jnp.asarray(onp.zeros((S,), onp.int32))
    hlo = model._step_fn.lower(
        model.params, cache._k, cache._v, zeros, zeros,
        *model.device_sampling(model.greedy_sampling(S))
    ).compile().as_text()
    module = hlo.split("\n", 1)[0]
    check("jit__step" in module,
          f"the decode step is not jit__step: {module[:80]}")
    n_buffer = int(cache.k(0).size)
    relayouts = cache_sized_relayouts(hlo, n_buffer)
    check(not relayouts,
          f"the decode step at {S} x {cache.bucket} relayouts whole KV "
          f"buffers, {len(relayouts)} instruction(s), the first: "
          f"{relayouts[:1]}")
    print(json.dumps({
        "phase": "decode", "ok": True, **device, "model": MODEL,
        "programs_warmed": warmed, "kv": cache.describe()["layout"],
        "step_shape": [S, cache.bucket], "kv_buffer_elements": n_buffer,
        "cache_sized_relayouts": len(relayouts),
        "seconds": round(time.perf_counter() - t_proc, 2)}), flush=True)


def child_train(phase, mesh_shape, ref_loss):
    t_proc = time.perf_counter()
    device = _require_tpu()
    import jax
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import _native, metrics
    from mxnet_tpu.gluon.model_zoo.gpt import get_gpt
    from mxnet_tpu.parallel import (DATA_PARALLEL_RULES,
                                    DEFAULT_TRANSFORMER_RULES, SPMDTrainer,
                                    make_mesh)

    n_dev = 1
    for n in mesh_shape.values():
        n_dev *= n
    check(device["count"] >= n_dev,
          f"mesh {mesh_shape} needs {n_dev} devices, jax reports "
          f"{device['count']}")

    mx.random.seed(0)
    net = get_gpt(MODEL, vocab_size=VOCAB, dropout=0.0, max_length=SEQ)
    net.initialize()
    net(mx.np.zeros((2, 16), dtype="int32"))
    net.cast("bfloat16")
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    trainer = SPMDTrainer(
        net, lambda o, l: loss_fn(o, l), optimizer="adamw",
        optimizer_params={"learning_rate": LEARNING_RATE},
        mesh=make_mesh(mesh_shape, devices=jax.devices()[:n_dev]),
        rules=(DEFAULT_TRANSFORMER_RULES if "tp" in mesh_shape
               else DATA_PARALLEL_RULES))
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randint(0, VOCAB, (BATCH, SEQ)).astype("int32"))
    y = mx.np.array(rng.randint(0, VOCAB, (BATCH, SEQ)).astype("int32"))
    setup_s = time.perf_counter() - t_proc

    t0 = time.perf_counter()
    losses = [float(trainer.step(x, y).asnumpy())
              for _ in range(WARMUP_STEPS)]
    warmup_s = time.perf_counter() - t0
    compiled = int(metrics.COMPILE_MISSES.value)
    cache_hits = int(metrics.COMPILE_PERSISTENT_HITS.value)
    step_s = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        losses.append(float(trainer.step(x, y).asnumpy()))
        step_s.append(round(time.perf_counter() - t0, 4))
    check(int(metrics.COMPILE_MISSES.value) == compiled
          and int(metrics.COMPILE_PERSISTENT_HITS.value) == cache_hits,
          "a program compiled after the warm-up steps")
    check(all(onp.isfinite(losses)), f"loss not finite: {losses}")
    check(losses[-1] < losses[0] and losses[-1] < losses[WARMUP_STEPS],
          f"loss did not fall: {losses}")

    params = [p.data()._data for p in trainer._params]
    _on_tpu(params, "parameter")
    _on_tpu(trainer._opt_states, "optimizer state")
    spread = None
    if n_dev > 1:
        name, arr = next(
            (n, a) for n, a, sh in zip(trainer._names, params,
                                       trainer._param_shardings)
            if "tp" in jax.tree_util.tree_leaves(tuple(sh.spec)))
        spread = len({s.device for s in arr.addressable_shards})
        check(spread == n_dev, f"{name} has shards on {spread} device(s), "
                               f"expected {n_dev}")
        shard_shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
        check(shard_shapes != {tuple(arr.shape)},
              f"{name} is replicated, not tp-sharded: {shard_shapes}")

    # the Mosaic kernel must be IN the compiled step, not beside it
    import jax.numpy as jnp
    args = (params, trainer._opt_states, jax.random.PRNGKey(0),
            jnp.float32(LEARNING_RATE), jnp.float32(0.0), trainer._t_dev,
            x._data, y._data)
    hlo = trainer._step_fn.lower(*args).compile().as_text()
    kernel_calls = hlo.count("tpu_custom_call")
    check(kernel_calls > 0, "no tpu_custom_call in the compiled step: "
                            "attention did not go through the Pallas kernel")

    result = {
        "phase": phase, "ok": True, **device,
        "jax": jax.__version__, "libmxtpu": _native.LIB is not None,
        "model": MODEL, "dtype": "bfloat16", "batch": [BATCH, SEQ],
        "mesh": mesh_shape, "setup_s": round(setup_s, 2),
        "warmup_s": round(warmup_s, 2), "programs_compiled": compiled,
        "persistent_cache_hits": cache_hits,
        "compile_s": round(metrics.COMPILE_SECONDS.sum, 2),
        "step_s": step_s, "losses": [round(l, 4) for l in losses],
        "kernel_calls_in_step": kernel_calls,
        "tp_param_devices": spread,
    }
    if ref_loss is None:
        result["kernel_vs_dense_rel_err"] = _kernel_parity()
    else:
        rel = abs(losses[0] - ref_loss) / abs(ref_loss)
        check(rel <= LOSS_MATCH_TOL,
              f"first-step loss {losses[0]} vs 1-chip {ref_loss}: "
              f"off by {rel:.4f} (> {LOSS_MATCH_TOL})")
        result["first_loss_vs_1chip_rel"] = round(rel, 5)
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# parent: never imports jax
# ---------------------------------------------------------------------------

def _kill_group(proc):
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def run_child(argv, deadline):
    """Run one phase child to its end; returns its result (the last
    stdout line).  A non-zero exit fails the smoke."""
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)]
                            + argv, cwd=HERE, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=max(1.0, deadline - time.time()))
    finally:
        _kill_group(proc)
    if proc.returncode != 0:
        sys.stderr.write(out)
    check(proc.returncode == 0,
          f"child {argv} exited with code {proc.returncode}")
    return json.loads(out.strip().splitlines()[-1])


def _get(port, path, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _metric(text, name):
    m = re.search(rf"^{name}(?:{{}})? ([0-9.e+-]+)$", text, re.M)
    check(m is not None, f"/metrics has no {name}")
    return float(m.group(1))


def _stream(port, tokens, max_new, out, i):
    """One streamed /v1/generate; out[i] = frames + timings."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300.0)
    t0 = time.perf_counter()
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(
            {"tokens": tokens, "max_new_tokens": max_new,
             "method": "greedy"}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        frames, ttft = [], None
        for line in resp:           # chunked NDJSON, one frame a line
            if line.strip():
                frames.append(json.loads(line))
                if ttft is None:
                    ttft = time.perf_counter() - t0
        out[i] = {"status": resp.status, "frames": frames, "ttft_s": ttft,
                  "total_s": time.perf_counter() - t0}
    finally:
        conn.close()


def _wave(port, requests):
    out = [None] * len(requests)
    threads = [threading.Thread(target=_stream, args=(port, t, n, out, i))
               for i, (t, n) in enumerate(requests)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=330.0)
    streams = []
    for (toks, max_new), r in zip(requests, out):
        check(r is not None, f"request of {len(toks)} tokens got no answer")
        check(r["status"] == 200, f"/v1/generate -> {r['status']}: "
                                  f"{r['frames']}")
        tok_frames = [f for f in r["frames"] if "token" in f]
        check([f["index"] for f in tok_frames]
              == list(range(len(tok_frames))),
              f"stream indexes not contiguous: {r['frames']}")
        check(len(tok_frames) == max_new,
              f"{len(tok_frames)} tokens streamed, asked for {max_new}")
        check(r["frames"][-1].get("done") is True,
              f"no done frame: {r['frames'][-1]}")
        streams.append([f["token"] for f in tok_frames])
    return streams, out


def phase_serve(deadline):
    t_spawn = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "tools", "serve.py"),
         "--generate", "--zoo-gpt", MODEL, "--port", "0",
         "--host", "127.0.0.1"],
        cwd=HERE, stdout=subprocess.PIPE, text=True, start_new_session=True)
    lines = []

    def pump():
        for line in proc.stdout:
            lines.append(line)
            sys.stderr.write("[serve] " + line)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        port = None
        while port is None:
            check(proc.poll() is None,
                  f"server exited with code {proc.returncode} before it "
                  "was ready")
            check(time.time() < deadline, "server not ready in time")
            for line in list(lines):
                m = re.search(r"serving on http://[^:]+:(\d+)", line)
                if m:
                    port = int(m.group(1))
            time.sleep(0.5)
        while _get(port, "/healthz")[0] != 200:
            check(time.time() < deadline, "/healthz never turned ready")
            time.sleep(0.5)
        boot_s = time.time() - t_spawn

        info = json.loads(_get(port, "/v1/model")[1])
        device, gen = info["device"], info["generation"]
        check(device["platform"] == "tpu",
              f"server computes on {device}, not on a TPU")
        check(gen["model"]["param_platforms"] == ["tpu"],
              f"server parameters on {gen['model']['param_platforms']}")
        check(gen["cache"]["platforms"] == ["tpu"],
              f"KV buffers on {gen['cache']['platforms']}")
        buckets, kv = gen["prompt_buckets"], gen["kv_buckets"]

        lengths, news = PROMPT_LENGTHS, NEW_TOKENS
        check(len({min(b for b in buckets if b >= n)
                   for n in lengths}) >= 2, f"prompts {lengths} share a "
              f"bucket of {buckets}")
        check(any(n <= kv[0] < n + new for n, new in zip(lengths, news)),
              f"no request outgrows the smallest KV bucket of {kv}")
        requests = [([(7 * i + 13 * j) % VOCAB for j in range(n)], new)
                    for i, (n, new) in enumerate(zip(lengths, news))]

        m0 = _get(port, "/metrics")[1]
        first, timing = _wave(port, requests)
        again, _ = _wave(port, requests)
        m1 = _get(port, "/metrics")[1]
        check(first == again,
              "the same greedy requests, sent twice, streamed different "
              f"tokens: {first} vs {again}")
        for name in ("mxnet_compile_misses_total",
                     "mxnet_compile_persistent_hits_total"):
            check(_metric(m1, name) == _metric(m0, name),
                  f"{name} moved while serving: {_metric(m0, name)} -> "
                  f"{_metric(m1, name)} (warm-up missed a program)")
        grown = _metric(m1, "mxnet_gen_kv_migrations_total") \
            - _metric(m0, "mxnet_gen_kv_migrations_total")
        check(grown >= 1, "no KV-bucket growth happened")

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=max(1.0, deadline - time.time()))
        check(rc == 0, f"server exited with code {rc} on SIGTERM")
    finally:
        _kill_group(proc)
        reader.join(timeout=5.0)
    return {
        "phase": "serve", "ok": True, **device, **info["runtime"],
        "model": MODEL, "dtype": gen["model"]["dtype"],
        "boot_s": round(boot_s, 2),
        "warmup_s": gen["warmup_seconds"],
        "programs_warmed": gen["warmed_programs"],
        "programs_compiled": int(_metric(m0, "mxnet_compile_misses_total")),
        "persistent_cache_hits": int(_metric(
            m0, "mxnet_compile_persistent_hits_total")),
        "prompt_lengths": lengths, "new_tokens": news,
        "ttft_s": [round(r["ttft_s"], 4) for r in timing],
        "request_s": [round(r["total_s"], 4) for r in timing],
        "kv_migrations": int(grown), "compiles_while_serving": 0,
        "sigterm_exit": 0,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", choices=("train", "train4", "decode"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--ref-loss", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "train":
        return child_train("train", {"dp": 1}, None)
    if args.child == "train4":
        return child_train("train4", {"dp": 2, "tp": 2}, args.ref_loss)
    if args.child == "decode":
        return child_decode()

    for need in ("mxnet_tpu/__init__.py", "tools/serve.py"):
        if not os.path.isfile(os.path.join(HERE, need)):
            sys.exit(f"chip_smoke: {need} not found next to this script; "
                     "run it from a checkout of the repo")
    deadline = time.time() + BUDGET_S
    train = run_child(["--child", "train"], deadline)
    print(json.dumps(train), flush=True)
    if train["count"] >= 4:
        train4 = run_child(["--child", "train4", "--ref-loss",
                            repr(train["losses"][0])], deadline)
    else:
        train4 = {"phase": "train4",
                  "skipped": f"{train['count']} device(s)"}
    print(json.dumps(train4), flush=True)
    print(json.dumps(run_child(["--child", "decode"], deadline)), flush=True)
    print(json.dumps(phase_serve(deadline)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": train["platform"], "kind": train["kind"],
        "count": train["count"]}}), flush=True)


if __name__ == "__main__":
    main()
