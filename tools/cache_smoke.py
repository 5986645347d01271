"""Warm-restart chaos smoke — the persistent compile cache's acceptance
gate (``ci/run.sh cache-smoke``, wired into tier1).

Proves the three claims that make compiled programs "checkpoint-grade"
artifacts (mxnet_tpu/compile_cache.py):

1. **Cold run compiles N** — a fresh training job (SPMDTrainer micro-
   fit) and a fresh serving replica (GenerationServer warmup + one
   streamed generation) each report >0 XLA backend compiles in their
   measurement window, and every program is durably written to the
   cache directory.
2. **Restarted run compiles 0** — the SAME jobs in fresh processes
   against the populated cache report ZERO XLA backend compiles in the
   same window (every program loads from disk), with **bit-identical
   losses and token streams** (a deserialized executable is the same
   compiled binary, not a recompile that may differ in the last ulp).
3. **A poisoned cache degrades, never fails** — with every entry
   corrupted (truncation, bit-flip, garbled manifest) AND a seeded
   ``compile_cache.read``/``compile_cache.write`` fault plan armed,
   the restarted jobs still complete with zero caller-visible errors
   and the same bit-identical outputs: every bad entry is quarantined
   (``mxnet_compile_cache_corrupt_total``) and silently recompiled.

The measurement window starts AFTER process setup (model init, eager
settle, shape-independent helper priming): restart economics are about
the expensive programs — train steps, prefill/decode/bucket grids —
not the microsecond zeros/split-key helpers a fresh process compiles
while booting.

Run directly::

    python tools/cache_smoke.py            # full gate (~1 min on CPU)
"""
import glob
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 4
GEN_TOKENS = 12


# ---------------------------------------------------------------------------
# children (fresh process per run: the restart IS the test)
# ---------------------------------------------------------------------------

def _child_train() -> None:
    """SPMD training job: K deterministic steps; prints losses +
    backend compiles observed in the measurement window."""
    import jax
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu import metrics as _m
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))                 # eager settle
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {"learning_rate": 0.05},
                          mesh=make_mesh({"dp": 1},
                                         devices=jax.devices()[:1]))
    # prime the shape-independent per-step helper OUTSIDE the window
    # (split_key compiles once per process, in microseconds — restart
    # cost lives in the step program)
    from mxnet_tpu.ndarray import random as _random
    _random.split_key()

    def batch(step):
        rng = onp.random.RandomState(100 + step)
        return (mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4")))

    c0 = _m.COMPILE_MISSES.value
    t0 = time.perf_counter()
    losses = []
    for s in range(STEPS):
        x, y = batch(s)
        losses.append(float(trainer.step(x, y).asnumpy()))
    print(json.dumps({
        "losses": losses,
        "compiles": _m.COMPILE_MISSES.value - c0,
        "seconds": time.perf_counter() - t0,
        "cache": cc.cache_stats(),
    }))


def _child_serve() -> None:
    """Serving replica: GenerationServer warmup (the full prefill /
    decode / KV program grid, before ready) + one streamed greedy
    generation; prints tokens + window compiles + warmup seconds."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu import metrics as _m
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                                   GenerationServer)

    mx.random.seed(0)
    gpt = GPTModel(vocab_size=97, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    gpt.initialize(mx.init.Normal(1.0))
    gpt(mx.np.zeros((1, 4), dtype="int32"))  # eager settle
    eng = GenerationEngine(DecodeModel.from_block(gpt), max_slots=2,
                           kv_buckets=(16, 32, 64), max_tokens=16)

    c0 = _m.COMPILE_MISSES.value
    with GenerationServer(eng, warmup=True) as gs:
        stream = gs.generate(onp.arange(1, 5, dtype="int32"),
                             max_new_tokens=GEN_TOKENS)
        toks = stream.result(timeout=120)
    print(json.dumps({
        "tokens": toks,
        "warmed": eng.warmed,
        "warmup_seconds": gs.warmup_seconds,
        "compiles": _m.COMPILE_MISSES.value - c0,
        "cache": cc.cache_stats(),
    }))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_child(role: str, cache_dir: str,
               fault_plan: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=cache_dir,
               PYTHONPATH=os.path.dirname(
                   os.path.dirname(os.path.abspath(__file__))))
    if fault_plan:
        env["MXNET_FAULT_PLAN"] = fault_plan
        env["MXNET_FAULT_SEED"] = "7"
    else:
        env.pop("MXNET_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", role],
        env=env, capture_output=True, text=True, timeout=420)
    if proc.returncode != 0:
        raise SystemExit(
            f"cache-smoke: {role} child FAILED (exit "
            f"{proc.returncode})\n--- stdout\n{proc.stdout}\n--- "
            f"stderr\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _corrupt_everything(cache_dir: str) -> int:
    """Poison every cache entry three different ways: truncate,
    bit-flip, garble-manifest — round-robin so each corruption class
    appears whenever there are >= 3 entries."""
    exes = sorted(glob.glob(os.path.join(cache_dir, "cc-*.exe")))
    for i, exe in enumerate(exes):
        mode = i % 3
        if mode == 0:
            with open(exe, "r+b") as f:
                f.truncate(16)
        elif mode == 1:
            with open(exe, "r+b") as f:
                data = bytearray(f.read())
                data[len(data) // 2] ^= 0xFF
                f.seek(0)
                f.write(data)
        else:
            man = exe[:-len(".exe")] + ".json"
            with open(man, "w") as f:
                f.write("{ not json")
    return len(exes)


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        {"train": _child_train, "serve": _child_serve}[sys.argv[2]]()
        return

    tmp = tempfile.mkdtemp(prefix="mxcc-smoke-")
    failures = []

    def check(cond, msg):
        print(("ok  " if cond else "FAIL") + f"  {msg}")
        if not cond:
            failures.append(msg)

    for role, outputs_key in (("train", "losses"), ("serve", "tokens")):
        cache_dir = os.path.join(tmp, role)
        cold = _run_child(role, cache_dir)
        warm = _run_child(role, cache_dir)
        n_entries = cold["cache"]["entries"]
        print(f"[{role}] cold: {cold['compiles']:.0f} XLA compiles, "
              f"{cold['cache']['writes']:.0f} cache writes, "
              f"{n_entries} entries on disk")
        print(f"[{role}] warm restart: {warm['compiles']:.0f} XLA "
              f"compiles, {warm['cache']['hits']:.0f} cache hits")
        if role == "serve":
            print(f"[serve] warmup {cold['warmed']} programs: "
                  f"{cold['warmup_seconds']:.2f}s cold -> "
                  f"{warm['warmup_seconds']:.2f}s warm")
        check(cold["compiles"] > 0,
              f"{role}: cold run compiles (got {cold['compiles']:.0f})")
        check(cold["cache"]["writes"] > 0 and n_entries > 0,
              f"{role}: cold run persisted its programs")
        check(warm["compiles"] == 0,
              f"{role}: restarted run compiles 0 in steady state "
              f"(got {warm['compiles']:.0f})")
        check(warm["cache"]["misses"] == 0,
              f"{role}: restarted run misses 0 "
              f"(got {warm['cache']['misses']:.0f})")
        check(cold[outputs_key] == warm[outputs_key],
              f"{role}: bit-identical {outputs_key} across restart")

        # chaos leg: every entry poisoned + seeded read/write faults —
        # must complete with zero caller-visible errors, identical
        # outputs, and every corrupt entry counted + quarantined
        poisoned = _corrupt_everything(cache_dir)
        chaos = _run_child(
            role, cache_dir,
            fault_plan=("compile_cache.read:p=0.3:kind=error;"
                        "compile_cache.write:p=0.3:kind=error"))
        print(f"[{role}] chaos: {poisoned} entries poisoned -> "
              f"{chaos['cache']['corrupt']:.0f} quarantined, "
              f"{chaos['compiles']:.0f} recompiles, 0 errors")
        check(chaos[outputs_key] == cold[outputs_key],
              f"{role}: poisoned-cache run still bit-identical")
        check(chaos["cache"]["corrupt"] > 0,
              f"{role}: corrupt entries counted "
              f"(got {chaos['cache']['corrupt']:.0f})")
        quarantined = glob.glob(os.path.join(cache_dir, "quarantine-*"))
        check(len(quarantined) > 0,
              f"{role}: corrupt entries quarantined aside "
              f"({len(quarantined)} files)")

    if failures:
        raise SystemExit("cache-smoke: FAILED\n  - "
                         + "\n  - ".join(failures))
    print("cache-smoke: PASSED (cold compiles persist, warm restarts "
          "compile 0 with bit-identical outputs, poisoned cache "
          "degrades to recompile with 0 errors)")


if __name__ == "__main__":
    main()
