"""A/B probe: Pallas prologue-fused 1x1 conv vs the unfused XLA chain.

Per-junction times at ResNet-50 b128 bottleneck shapes, measured as a
lax.scan of ITERS repetitions inside ONE jit — the per-call dispatch
floor otherwise swamps sub-ms kernels (the first version of this probe
measured pure dispatch).  Each scan iteration
depends on the previous through a scalar, so XLA cannot batch or DCE
the op; the reported time is (t_scan - t_null) / ITERS.

Junction 3 (affine+relu -> conv3) and junction 1 (relu -> next conv1)
shapes; fwd and fwd+bwd arms, fused vs unfused.

Usage: python benchmark/fused_conv_probe.py [batch]
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mxnet_tpu.ops.pallas.conv_fused import fused_prologue_conv1x1

# (Ci, Co, HW) at b128 — junction 3 (affine+relu) then junction 1 (relu)
J3 = [(64, 256, 56), (128, 512, 28), (256, 1024, 14), (512, 2048, 7)]
J1 = [(256, 64, 56), (512, 128, 28), (1024, 256, 14), (2048, 512, 7)]
ITERS = 20


def timed(fn, *args, n=5, static=()):
    import numpy as onp
    f = jax.jit(fn, static_argnums=static)
    # the host fetch is the sync point (outputs here are scalars)
    onp.asarray(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        onp.asarray(f(*args))   # fetch forces execution; RTT cancels
    t1 = time.perf_counter()    # against the null-scan arm
    return (t1 - t0) / n


def unfused(x, w, scale, shift, affine):
    a = x.astype(jnp.float32)
    if affine:
        a = a * scale.reshape(1, -1, 1, 1) + shift.reshape(1, -1, 1, 1)
    h = jnp.maximum(a, 0.0).astype(x.dtype)
    return lax.conv_general_dilated(
        h, w[:, :, None, None], (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def fused(x, w, scale, shift, affine):
    return fused_prologue_conv1x1(x, w, scale if affine else None,
                                  shift if affine else None, relu=True)


def scan_fwd(impl, x, w, scale, shift, affine):
    def body(c, _):
        y = impl(x + c.astype(x.dtype), w, scale, shift, affine)
        # full-tensor reduction: a single-element carry lets XLA slice
        # the whole conv away (the first version measured nothing)
        return jnp.max(y).astype(jnp.float32) * 1e-9, None
    c, _ = lax.scan(body, jnp.float32(0), None, length=ITERS)
    return c


def scan_bwd(impl, x, w, scale, shift, affine, dy):
    if affine:
        def f(x, w, scale, shift):
            y = impl(x, w, scale, shift, True)
            return jnp.sum(y.astype(jnp.float32) * dy)
        g = jax.grad(f, argnums=(0, 1, 2, 3))
        def body(c, _):
            gx, gw, gs, gt = g(x + c.astype(x.dtype), w, scale, shift)
            return (jnp.max(gx).astype(jnp.float32)
                    + jnp.max(gw).astype(jnp.float32)
                    + jnp.max(gs) + jnp.max(gt)) * 1e-9, None
    else:
        def f(x, w):
            y = impl(x, w, None, None, False)
            return jnp.sum(y.astype(jnp.float32) * dy)
        g = jax.grad(f, argnums=(0, 1))
        def body(c, _):
            gx, gw = g(x + c.astype(x.dtype), w)
            return (jnp.max(gx).astype(jnp.float32)
                    + jnp.max(gw).astype(jnp.float32)) * 1e-9, None
    c, _ = lax.scan(body, jnp.float32(0), None, length=ITERS)
    return c


def scan_null(x):
    def body(c, _):
        return c + x.astype(jnp.float32)[0, 0, 0, 0] * 1e-9, None
    c, _ = lax.scan(body, jnp.float32(0), None, length=ITERS)
    return c


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    key = jax.random.PRNGKey(0)
    for affine, shapes, tag in [(True, J3, "j3 bn+relu->1x1"),
                                (False, J1, "j1    relu->1x1")]:
        for Ci, Co, HW in shapes:
            ks = jax.random.split(key, 5)
            x = jax.random.normal(ks[0], (B, Ci, HW, HW)).astype(jnp.bfloat16)
            w = (jax.random.normal(ks[1], (Co, Ci)) * 0.05).astype(jnp.bfloat16)
            scale = jax.random.uniform(ks[2], (Ci,)) + 0.5
            shift = jax.random.normal(ks[3], (Ci,)) * 0.1
            dy = jax.random.normal(ks[4], (B, Co, HW, HW)).astype(jnp.float32)
            x, w, scale, shift, dy = jax.device_put((x, w, scale, shift, dy))

            import functools
            t0 = timed(scan_null, x)
            per = {}
            for name, impl in (("ref", unfused), ("fus", fused)):
                # arrays ride as jit ARGUMENTS — a closure capture would
                # embed them as HLO constants
                tf = (timed(functools.partial(scan_fwd, impl),
                            x, w, scale, shift, affine,
                            static=(4,)) - t0) / ITERS
                tb = (timed(functools.partial(scan_bwd, impl),
                            x, w, scale, shift, affine, dy,
                            static=(4,)) - t0) / ITERS
                per[name] = (tf, tb)
            rf, rb = per["ref"]
            ff, fb = per["fus"]
            print(f"{tag} Ci={Ci:4d} Co={Co:4d} {HW}x{HW}: "
                  f"fwd {rf*1e3:6.2f} -> {ff*1e3:6.2f} ms ({rf/ff:4.2f}x) | "
                  f"fwd+bwd {rb*1e3:6.2f} -> {fb*1e3:6.2f} ms "
                  f"({rb/fb:4.2f}x)", flush=True)


if __name__ == "__main__":
    main()
