"""Raw-jax ResNet-50 step-time probe: what can XLA itself do on this chip?

Measures fwd / fwd+bwd / fwd+bwd+sgd step time for a hand-rolled ResNet-50
in NCHW and NHWC layouts, bf16, outside the framework. This separates
"mxnet_tpu overhead" from "XLA conv behavior" when chasing BASELINE
config 2. Not a framework API — a diagnostic harness.

Usage: python benchmark/xla_resnet_probe.py [nchw|nhwc] [batch]
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax


def conv(x, w, stride, layout):
    if layout == "NCHW":
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        dn = ("NHWC", "HWIO", "NHWC")
    kh = w.shape[2] if layout == "NCHW" else w.shape[0]
    pad = (kh - 1) // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=dn)


def bn(x, scale, bias, layout):
    axes = (0, 2, 3) if layout == "NCHW" else (0, 1, 2)
    xf = x.astype(jnp.float32)
    m = xf.mean(axes, keepdims=True)
    v = xf.var(axes, keepdims=True)
    y = (xf - m) * lax.rsqrt(v + 1e-5)
    shape = [1, -1, 1, 1] if layout == "NCHW" else [1, 1, 1, -1]
    return (y * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)


def make_params(rng, layout, dtype=jnp.bfloat16):
    """ResNet-50 v1: stem + [3,4,6,3] bottleneck stages + fc."""
    params = []
    keys = iter(jax.random.split(rng, 256))

    def w_conv(cin, cout, k):
        shape = ((cout, cin, k, k) if layout == "NCHW"
                 else (k, k, cin, cout))
        fan_in = cin * k * k
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (2.0 / fan_in) ** 0.5).astype(dtype)

    def w_bn(c):
        return (jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32))

    stem = {"w": w_conv(3, 64, 7), "bn": w_bn(64)}
    stages = []
    cin = 64
    for stage_i, (blocks, cmid) in enumerate(
            zip([3, 4, 6, 3], [64, 128, 256, 512])):
        cout = cmid * 4
        stage = []
        for b in range(blocks):
            stride = 2 if (b == 0 and stage_i > 0) else 1
            blk = {
                "c1": w_conv(cin, cmid, 1), "bn1": w_bn(cmid),
                "c2": w_conv(cmid, cmid, 3), "bn2": w_bn(cmid),
                "c3": w_conv(cmid, cout, 1), "bn3": w_bn(cout),
            }
            if cin != cout or stride != 1:
                blk["proj"] = w_conv(cin, cout, 1)
                blk["bnp"] = w_bn(cout)
            stage.append(blk)
            cin = cout
        stages.append(stage)
    fc_w = (jax.random.normal(next(keys), (2048, 1000), jnp.float32)
            * 0.01).astype(dtype)
    fc_b = jnp.zeros((1000,), dtype)
    return {"stem": stem, "stages": stages, "fc": (fc_w, fc_b)}


def forward(params, x, layout):
    h = conv(x, params["stem"]["w"], 2, layout)
    h = jax.nn.relu(bn(h, *params["stem"]["bn"], layout))
    if layout == "NCHW":
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    else:
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for stage_i, stage in enumerate(params["stages"]):
        for b, blk in enumerate(stage):
            s = 2 if (b == 0 and stage_i > 0) else 1
            r = h
            h2 = jax.nn.relu(bn(conv(h, blk["c1"], 1, layout),
                                *blk["bn1"], layout))
            h2 = jax.nn.relu(bn(conv(h2, blk["c2"], s, layout),
                                *blk["bn2"], layout))
            h2 = bn(conv(h2, blk["c3"], 1, layout), *blk["bn3"], layout)
            if "proj" in blk:
                r = bn(conv(r, blk["proj"], s, layout),
                       *blk["bnp"], layout)
            h = jax.nn.relu(h2 + r)
    axes = (2, 3) if layout == "NCHW" else (1, 2)
    pooled = h.astype(jnp.float32).mean(axes)
    w, b = params["fc"]
    return pooled @ w.astype(jnp.float32) + b.astype(jnp.float32)


def loss_fn(params, x, y, layout):
    logits = forward(params, x, layout)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
    return (lse - true).mean()


def time_call(fn, *args, n=20):
    r = fn(*args)
    r = fn(*args)  # relayout recompile
    leaves = jax.tree_util.tree_leaves(r)
    onp.asarray(leaves[0]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    leaves = jax.tree_util.tree_leaves(r)
    onp.asarray(leaves[0]).ravel()[:1]
    return (time.perf_counter() - t0) / n


def main():
    layout = sys.argv[1].upper() if len(sys.argv) > 1 else "NCHW"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    rng = jax.random.PRNGKey(0)
    params = jax.device_put(make_params(rng, layout), jax.devices()[0])
    shape = ((batch, 3, 224, 224) if layout == "NCHW"
             else (batch, 224, 224, 3))
    x = jax.device_put(
        jnp.asarray(onp.random.RandomState(0).uniform(-1, 1, shape),
                    jnp.bfloat16), jax.devices()[0])
    y = jax.device_put(
        jnp.asarray(onp.random.RandomState(1).randint(0, 1000, (batch,)),
                    jnp.int32), jax.devices()[0])

    # all timed jits return SCALARS: the host fetch that ends the timed
    # region then copies nothing large back.
    fwd = jax.jit(functools.partial(loss_fn, layout=layout))
    dt = time_call(fwd, params, x, y)
    print(f"[{layout} b{batch}] fwd+loss     {dt*1e3:7.2f} ms "
          f"({batch/dt:7.1f} img/s)")

    @jax.jit
    def grad_scalar(params, x, y):
        g = jax.grad(functools.partial(loss_fn, layout=layout))(params, x, y)
        return sum(l.astype(jnp.float32).sum()
                   for l in jax.tree_util.tree_leaves(g))

    dt = time_call(grad_scalar, params, x, y)
    print(f"[{layout} b{batch}] fwd+bwd      {dt*1e3:7.2f} ms "
          f"({batch/dt:7.1f} img/s)")


if __name__ == "__main__":
    main()
