"""Per-stage/per-component attribution of the ResNet-50 b128 train step.

VERDICT r4 weak 1: the headline has been flat at ~2,470 img/s while the
roofline proves the conv shapes run at 151-190 TFLOP/s in isolation —
so where do the milliseconds actually go?  This probe answers by
DIFFERENCE (the roofline's method, robust to fixed per-call costs):

* truncated networks (stem, +stage1, ..., +stage4, +head) — successive
  differences attribute fwd+bwd time per stage;
* component ablations at the full depth — batch-stat BN swapped for a
  frozen scale/bias (quantifies the stats round-trips), ReLU removed
  (quantifies activation fusion), convs-only;
* a per-shape conv roofline check inside the real context.

All variants time fwd+bwd+(sgd update) of the SAME hand-rolled bf16
NCHW ResNet-50 as xla_resnet_probe (raw jax — framework overhead is
already known to be ~nil: raw 2,276 img/s vs framework 2,469).

Usage: python benchmark/resnet_layer_probe.py [batch]
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

sys.path.insert(0, "/root/repo")
from benchmark.xla_resnet_probe import (bn, conv, forward, loss_fn,
                                        make_params)


def bn_frozen(x, scale, bias, layout):
    """Scale/bias only — no batch statistics (the ablation arm)."""
    shape = [1, -1, 1, 1] if layout == "NCHW" else [1, 1, 1, -1]
    xf = x.astype(jnp.float32)
    return (xf * scale.reshape(shape)
            + bias.reshape(shape)).astype(x.dtype)


def forward_ablate(params, x, layout, bn_fn, use_relu=True, depth=99):
    """forward() with swappable BN/ReLU and a stage-truncation depth:
    depth 0 = stem only, 1..4 = through stage N, 99 = full net."""
    act = jax.nn.relu if use_relu else (lambda a: a)
    h = conv(x, params["stem"]["w"], 2, layout)
    h = act(bn_fn(h, *params["stem"]["bn"], layout))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    if depth == 0:
        return h
    for stage_i, stage in enumerate(params["stages"]):
        if stage_i >= depth:
            return h
        for b, blk in enumerate(stage):
            s = 2 if (b == 0 and stage_i > 0) else 1
            r = h
            h2 = act(bn_fn(conv(h, blk["c1"], 1, layout),
                           *blk["bn1"], layout))
            h2 = act(bn_fn(conv(h2, blk["c2"], s, layout),
                           *blk["bn2"], layout))
            h2 = bn_fn(conv(h2, blk["c3"], 1, layout), *blk["bn3"], layout)
            if "proj" in blk:
                r = bn_fn(conv(r, blk["proj"], s, layout),
                          *blk["bnp"], layout)
            h = act(h2 + r)
    pooled = h.astype(jnp.float32).mean((2, 3))
    w, b = params["fc"]
    return pooled @ w.astype(jnp.float32) + b.astype(jnp.float32)


def timed_grad(fn, params, x, y, n=20):
    g = jax.jit(jax.grad(fn))
    r = g(params, x, y)
    r = g(params, x, y)
    jax.tree_util.tree_leaves(r)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        r = g(params, x, y)
    jax.tree_util.tree_leaves(r)[0].block_until_ready()
    return (time.perf_counter() - t0) / n


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    layout = "NCHW"
    rng = jax.random.PRNGKey(0)
    params = make_params(rng, layout)
    params = jax.device_put(params)
    x = jax.device_put(
        jax.random.normal(rng, (B, 3, 224, 224), jnp.float32)
        .astype(jnp.bfloat16))
    y = jax.device_put(
        jax.random.randint(rng, (B,), 0, 1000, jnp.int32))

    def loss_of(bn_fn, use_relu=True, depth=99):
        def f(p, x, y):
            h = forward_ablate(p, x, layout, bn_fn, use_relu, depth)
            if depth != 99:
                return (h.astype(jnp.float32) ** 2).mean()
            lse = jax.nn.logsumexp(h, axis=-1)
            true = jnp.take_along_axis(h, y[:, None], 1)[:, 0]
            return (lse - true).mean()
        return f

    full = timed_grad(loss_of(bn), params, x, y)
    print(f"full fwd+bwd           {full * 1e3:8.2f} ms "
          f"({B / full:7.1f} img/s)")

    nobn = timed_grad(loss_of(bn_frozen), params, x, y)
    print(f"frozen-BN (no stats)   {nobn * 1e3:8.2f} ms "
          f"(stats cost {1e3 * (full - nobn):6.2f} ms)")

    norelu = timed_grad(loss_of(bn, use_relu=False), params, x, y)
    print(f"no-ReLU                {norelu * 1e3:8.2f} ms "
          f"(relu cost {1e3 * (full - norelu):6.2f} ms)")

    both = timed_grad(loss_of(bn_frozen, use_relu=False), params, x, y)
    print(f"convs+residual only    {both * 1e3:8.2f} ms")

    prev = 0.0
    for depth, name in [(0, "stem+pool"), (1, "stage1"), (2, "stage2"),
                        (3, "stage3"), (4, "stage4")]:
        t = timed_grad(loss_of(bn, depth=depth), params, x, y)
        print(f"through {name:<10}     {t * 1e3:8.2f} ms "
              f"(+{1e3 * (t - prev):6.2f} ms)")
        prev = t


if __name__ == "__main__":
    main()
