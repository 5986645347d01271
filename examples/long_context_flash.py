#!/usr/bin/env python
"""Long-context attention with the Pallas flash kernel.

Demonstrates the round-2 kernel surface: additive bias/attention masks
streamed blockwise, attention-probability dropout from the TPU PRNG
(regenerable per-tile masks, so backward needs no stored mask), and
tunable block sizes (MXNET_FLASH_BLOCK_Q/K). On CPU the kernels run in
interpret mode (dropout takes a dense fallback); on TPU they compile via
Mosaic — scores never materialize in HBM, so sequence length scales past
the O(T^2) wall (BASELINE.md configs 3b/6b).

    python examples/long_context_flash.py --seq 4096        # real chip
    python examples/long_context_flash.py --seq 512 --force-cpu
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--dropout", type=float, default=0.1)
    ap.add_argument("--force-cpu", action="store_true")
    args = ap.parse_args()

    if args.force_cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.ops.pallas.attention import flash_attention

    B, T, H, D = args.batch, args.seq, args.heads, args.head_dim
    rng = onp.random.RandomState(0)
    dev = jax.devices()[0]
    q = jax.device_put(jnp.asarray(
        rng.uniform(-1, 1, (B, T, H, D)), jnp.bfloat16), dev)
    print(f"attention over B={B} T={T} H={H} D={D} "
          f"({jax.default_backend()} backend)")

    # causal + ALiBi-style additive bias (broadcast over batch and heads)
    pos = onp.arange(T)
    alibi = -0.05 * onp.abs(pos[None, :] - pos[:, None])
    bias = jax.device_put(jnp.asarray(
        alibi[None, None], jnp.float32), dev)
    seed = jnp.asarray([1234, 5678], jnp.int32)

    @jax.jit
    def step(q, bias):
        out = flash_attention(q, q, q, causal=True, bias=bias,
                              bias_grad=False,        # mask, not learned
                              dropout=args.dropout, dropout_seed=seed)
        return out.astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(lambda q, b: step(q, b)))
    val = step(q, bias)
    g = grad(q, bias)
    print("loss:", float(val), "| grad finite:",
          bool(jnp.isfinite(g.astype(jnp.float32)).all()))

    # steady-state timing; step() is already compiled and blocked above.
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        v = step(q, bias)
    float(v)
    dt = (time.perf_counter() - t0) / n
    flops = 4 * B * H * T * T * D  # qk + pv, causal halves it roughly
    print(f"fwd: {dt*1e3:.2f} ms/call  (~{flops/dt/1e12:.1f} TFLOP/s "
          f"upper bound, causal ~halves)")


if __name__ == "__main__":
    main()
