"""Flash attention — blockwise online-softmax Pallas kernel.

Reference parity (leezu/mxnet): the reference's attention is full O(T²)
materialized scores (``src/operator/contrib/transformer.cu``); this kernel
is the TPU-native upgrade (SURVEY.md 5.7): tiles of Q stream over tiles of
K/V held in VMEM with a running max/denominator, so scores never hit HBM.

Forward is the Pallas kernel (grid B×H×Tq-blocks×Tk-blocks, sequential
accumulation over the last grid axis in VMEM scratch), emitting the
per-row log-sum-exp. Backward is blockwise too (standard flash-attention
recipe): a dq kernel streams K/V blocks against the saved LSE and
``delta = rowsum(dO·O)``, and a dk/dv kernel streams Q/dO blocks — scores
are recomputed per tile and never hit HBM in either direction.

Surface (round-2): additive bias/mask blocks stream like K/V (broadcast
(1|B, 1|H, Tq, Tk) accepted; the bias gradient materializes the softmax
cotangent ds, O(B·H·T²) — the price of a dense bias); probability dropout
uses the TPU PRNG seeded per (batch, head, q-block, k-block) tile so the
backward kernels regenerate the identical mask; block sizes are tunable
per call. On CPU the kernels run in interpret mode, except dropout which
takes a dense XLA path (pltpu PRNG is TPU-only).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# all three kernels accumulate over their LAST grid axis only; telling
# Mosaic the rest are parallel lets it pipeline/reorder grid steps
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _prec(dtype):
    """Explicit dot precision per operand dtype: the kernel's contract is
    bf16 MXU passes for low-precision inputs and exact fp32 for f32 —
    INDEPENDENT of the global jax_default_matmul_precision (a global
    'highest' would otherwise request an fp32 contract on bf16 operands,
    which Mosaic rejects with 'Bad lhs type')."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _bias_spec(bias_shape, block_q, block_k, kv_major: bool = False):
    """Bias streams like K/V. A Tq-broadcast bias (B/1, H/1, 1, Tk) —
    the canonical BERT key-padding mask — ships as (1, block_k) rows
    that broadcast over the q tile inside the kernel; a full bias ships
    (block_q, block_k) tiles. ``kv_major`` flips the grid argument
    order for the dkv kernel's (b, h, ik, iq) grid."""
    Bb, Hb, Tqb = bias_shape[0], bias_shape[1], bias_shape[2]

    def idx(b, h, x, y):
        i, j = (y, x) if kv_major else (x, y)
        return (b if Bb > 1 else 0, h if Hb > 1 else 0,
                0 if Tqb == 1 else i, j)

    if Tqb == 1:
        return pl.BlockSpec((1, 1, 1, block_k), idx)
    return pl.BlockSpec((1, 1, block_q, block_k), idx)


_MIX_B, _MIX_H = 1000003, 7919      # per-batch / per-head seed strides


def _dropout_keep(seed_ref, b, h, iq, ik, rate, shape):
    """Regenerable keep-mask for one (q-block, k-block) tile: seeding is a
    pure function of (user seed, batch, head, q-block, k-block), so the
    dq/dkv kernels rebuild the identical mask. Mosaic caps prng_seed at
    two words, so the tile coordinates fold in arithmetically (int32
    wraparound is deterministic)."""
    mix0 = seed_ref[0] + b * jnp.int32(_MIX_B) + h * jnp.int32(_MIX_H)
    mix1 = seed_ref[1] + iq * jnp.int32(65537) + ik
    pltpu.prng_seed(mix0, mix1)
    bits = pltpu.prng_random_bits(shape)
    threshold = jnp.uint32(min(0xFFFFFFFF, int(rate * 4294967296.0)))
    return bits.astype(jnp.uint32) >= threshold


def dropout_seed_at(seed, b0, h0):
    """The seed under which a kernel call whose local (batch, head)
    origin sits at global ``(b0, h0)`` regenerates exactly the tiles
    :func:`_dropout_keep` gives the unsharded call (same mix, shifted)."""
    return seed + jnp.stack([
        b0 * jnp.int32(_MIX_B) + h0 * jnp.int32(_MIX_H),
        jnp.int32(0)]).astype(seed.dtype)


def _causal_branches(causal, iq, ik, block_q, block_k, kv_len, tile,
                     skipped=None):
    """Dispatch one grid step to the right specialization of ``tile``:

    - fully-masked tiles (above the causal diagonal) execute NOTHING —
      at T=1024/128-blocks this halves the kernel's matmul work, the
      reason a causal flash kernel can beat XLA's full-T² attention;
    - interior tiles (fully below the diagonal, inside kv range) skip
      the iota/compare/where masking entirely;
    - only diagonal-straddling or kv-padded tiles pay the masked path.
    All conditions are scalar functions of the grid ids, so Mosaic
    executes exactly one branch per step."""
    need_kv = (ik + 1) * block_k > kv_len
    if causal:
        live = ik * block_k <= (iq + 1) * block_q - 1
        need_mask = jnp.logical_or(
            (ik + 1) * block_k - 1 > iq * block_q, need_kv)

        @pl.when(jnp.logical_and(live, jnp.logical_not(need_mask)))
        def _fast():
            tile(False)

        @pl.when(jnp.logical_and(live, need_mask))
        def _masked():
            tile(True)

        if skipped is not None:
            @pl.when(jnp.logical_not(live))
            def _skip():
                skipped()
    else:
        @pl.when(jnp.logical_not(need_kv))
        def _fast():
            tile(False)

        @pl.when(need_kv)
        def _masked():
            tile(True)


def _flash_fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, kv_len: int, num_k_blocks: int,
                      has_bias: bool, rate: float):
    i = 0
    q_ref, kt_ref, v_ref = refs[0], refs[1], refs[2]
    i = 3
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    seed_ref = refs[i] if rate > 0 else None
    i += 1 if rate > 0 else 0
    o_ref, lse_ref = refs[i], refs[i + 1]
    if num_k_blocks > 1:
        acc_ref, m_ref, l_ref = refs[i + 2:i + 5]

    b = pl.program_id(0)
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    if num_k_blocks == 1:
        # single-block specialization (every T <= block_k): the whole K
        # is in this tile, so the online-softmax carry (acc rescale,
        # running m/l scratch reads/writes) is pure overhead — a plain
        # row softmax computes the exact same result ~15% faster.
        def tile1(apply_mask):
            q = q_ref[0, 0]
            kt = kt_ref[0, 0]
            v = v_ref[0, 0]
            s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=_prec(q.dtype)) * scale
            if has_bias:
                s = s + bias_ref[0, 0].astype(jnp.float32)
            if apply_mask:
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                mask = col < kv_len
                if causal:
                    row = iq * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    mask = jnp.logical_and(mask, col <= row)
                s = jnp.where(mask, s, _NEG_INF)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            if rate > 0:
                keep = _dropout_keep(seed_ref, b, h, iq, ik, rate,
                                     p.shape)
                p = jnp.where(keep, p / (1.0 - rate), 0.0)
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(v.dtype))
            denom = jnp.maximum(l, 1e-30)
            o_ref[0, 0] = (acc / denom).astype(o_ref.dtype)
            lse_ref[0, 0] = m + jnp.log(denom)

        _causal_branches(causal, iq, ik, block_q, block_k, kv_len, tile1)
        return

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(apply_mask):
        q = q_ref[0, 0]                               # (bq, d) input dtype
        kt = kt_ref[0, 0]                             # (d, bk) PRE-transposed
        v = v_ref[0, 0]                               # (bk, d)
        # matmuls run in the INPUT dtype (bf16 MXU rate is 2-4x f32) with
        # f32 accumulation; scale applies to the f32 product.  K arrives
        # PRE-TRANSPOSED (r5): contracting over the rhs's LANE dim (the
        # q@k^T 'nt' form) makes Mosaic transpose k inside every grid
        # step — a measured 27% of the whole fwd kernel at BERT shapes;
        # the one XLA-side swapaxes outside the kernel costs ~0.2 ms
        # and every step's matmul becomes MXU-native.
        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if apply_mask:
            col = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = col < kv_len
            if causal:
                row = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                mask = jnp.logical_and(mask, col <= row)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (bq, bk) f32
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0:
            keep = _dropout_keep(seed_ref, b, h, iq, ik, rate, p.shape)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(v.dtype))
        m_ref[...] = m_new

    _causal_branches(causal, iq, ik, block_q, block_k, kv_len, tile)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # lse rides as (B, H, T, 1): a trailing unit dim keeps the block
        # shape (block_q, 1) legal under TPU (8, 128) tiling rules
        lse_ref[0, 0] = m_ref[...] + jnp.log(denom)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _legal_blocks(block_q, block_k, Tq, Tk, interpret):
    """TPU tiling legality: a block's trailing dim must be a multiple
    of 128 or the whole (padded) axis, second-to-last a multiple of 8
    or whole.  Since r5 the K/V operands ship PRE-TRANSPOSED, putting
    ``block_k`` on the LANE dim of the (D, block_k) kT/vT blocks — so
    the constraint applies to EVERY call, not only blocked-bias ones:
    odd tunable blocks collapse to whole-axis blocks (same math, one
    block).  Interpret mode (CPU) keeps the requested blocks for
    multi-block coverage."""
    if not interpret:
        if block_k % 128:
            block_k = Tk
        if block_q % 8:
            block_q = Tq
    return block_q, block_k


def _pad_bias(bias, block_q, block_k):
    if bias.shape[2] == 1:          # Tq-broadcast row bias: pad Tk only
        return _pad_to(bias, 3, block_k)
    return _pad_to(_pad_to(bias, 2, block_q), 3, block_k)


def _flash_forward(q, k, v, bias, seed, scale: float, causal: bool,
                   block_q: int, block_k: int, rate: float,
                   interpret: bool):
    """q/k/v: (B, H, T, D). Returns ((B, H, Tq, D), lse (B, H, Tq, 1))."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    has_bias = bias is not None
    block_q, block_k = _legal_blocks(block_q, block_k, Tq, Tk,
                                     interpret)
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    Tq_p, Tk_p = qp.shape[2], kp.shape[2]
    n_q, n_k = Tq_p // block_q, Tk_p // block_k
    # K ships PRE-TRANSPOSED (one XLA copy) so the in-kernel q@k^T is an
    # MXU-native 'nn' contraction — see _flash_fwd_kernel
    ktp = jnp.swapaxes(kp, 2, 3)                      # (B, H, D, Tk_p)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=Tk, num_k_blocks=n_k, has_bias=has_bias,
        rate=rate)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, D, block_k), lambda b, h, i, j: (b, h, 0, j)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
    ]
    args = [qp, ktp, vp]
    if has_bias:
        bp = _pad_bias(bias, block_q, block_k)
        in_specs.append(_bias_spec(bias.shape, block_q, block_k))
        args.append(bp)
    if rate > 0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq_p, 1), jnp.float32),
        ],
        # the single-block specialization needs no online-softmax carry —
        # don't reserve VMEM it never touches
        scratch_shapes=([] if n_k == 1 else [
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ]),
        interpret=interpret,
        compiler_params=_GRID_SEMANTICS,
    )(*args)
    return out[:, :, :Tq], lse[:, :, :Tq]


def _flash_bwd_dq_kernel(*refs, scale: float, causal: bool, block_q: int,
                         block_k: int, kv_len: int, num_k_blocks: int,
                         has_bias: bool, rate: float, emit_ds: bool):
    i = 0
    (q_ref, k_ref, kt_ref, vt_ref, do_ref, lse_ref,
     delta_ref) = refs[:7]
    i = 7
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    seed_ref = refs[i] if rate > 0 else None
    i += 1 if rate > 0 else 0
    dq_ref = refs[i]
    ds_ref = refs[i + 1] if emit_ds else None
    dq_acc = refs[i + 2] if emit_ds else refs[i + 1]

    b = pl.program_id(0)
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(apply_mask):
        q = q_ref[0, 0]                                # (bq, d) input dtype
        k = k_ref[0, 0]                                # (bk, d)
        kt = kt_ref[0, 0]                              # (d, bk)
        vt = vt_ref[0, 0]                              # (d, bk)
        do = do_ref[0, 0]                              # (bq, d)
        lse = lse_ref[0, 0]                            # (bq, 1)
        delta = delta_ref[0, 0]                        # (bq, 1)

        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        p = jnp.exp(s - lse)                           # (bq, bk) f32
        if apply_mask:
            col = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = col < kv_len
            if causal:
                row = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                mask = jnp.logical_and(mask, col <= row)
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(vt.dtype))
        if rate > 0:
            keep = _dropout_keep(seed_ref, b, h, iq, ik, rate, p.shape)
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        ds0 = p * (dp - delta)                         # dsoftmax (no scale)
        if emit_ds:
            ds_ref[0, 0] = ds0.astype(ds_ref.dtype)
        ds = (ds0 * scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(k.dtype))

    def skipped():
        if emit_ds:
            ds_ref[0, 0] = jnp.zeros_like(ds_ref[0, 0])

    _causal_branches(causal, iq, ik, block_q, block_k, kv_len, tile,
                     skipped=skipped if emit_ds else None)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_fused_kernel(*refs, scale: float, causal: bool,
                            block_q: int, block_k: int, kv_len: int,
                            num_q_blocks: int, has_bias: bool,
                            rate: float, emit_ds: bool):
    """Single-pass backward for the n_k == 1 regime (Tk fits one k-block
    — every T <= block_k, i.e. all BERT/GPT headline shapes under the
    default 1024 block).  The two-pass recipe pays two kernel launches
    that each re-read q/k/v and re-compute the probabilities; here one
    grid (B, H, n_q) computes s and p ONCE per q-tile, emits dq directly
    (the whole K is resident, so dq needs no cross-block accumulation),
    and accumulates dk/dv in VMEM scratch over the sequential q axis.
    K/V block specs are constant in iq, so Mosaic keeps them in VMEM
    across the whole (b, h) pass — q/k/v stream exactly once.  K rides
    twice (original for ds@k, pre-transposed for q@k^T) and V rides
    only pre-transposed (do@v^T) — r5: shipping the transposed forms
    keeps every matmul MXU-native instead of paying an in-kernel
    transpose per grid step."""
    (q_ref, k_ref, kt_ref, vt_ref, do_ref, lse_ref,
     delta_ref) = refs[:7]
    i = 7
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    seed_ref = refs[i] if rate > 0 else None
    i += 1 if rate > 0 else 0
    dq_ref, dk_ref, dv_ref = refs[i:i + 3]
    i += 3
    ds_ref = refs[i] if emit_ds else None
    i += 1 if emit_ds else 0
    dk_acc, dv_acc = refs[i:i + 2]

    b = pl.program_id(0)
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ik = 0                          # the single k block

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(apply_mask):
        q = q_ref[0, 0]                                # (bq, d) input dtype
        k = k_ref[0, 0]                                # (Tk, d)
        kt = kt_ref[0, 0]                              # (d, Tk)
        vt = vt_ref[0, 0]                              # (d, Tk)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                            # (bq, 1)
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        p = jnp.exp(s - lse)                           # (bq, Tk) f32
        if apply_mask:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = col < kv_len
            if causal:
                row = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                mask = jnp.logical_and(mask, col <= row)
            p = jnp.where(mask, p, 0.0)
        p_drop = p
        dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(vt.dtype))
        if rate > 0:
            keep = _dropout_keep(seed_ref, b, h, iq, ik, rate, p.shape)
            inv = 1.0 / (1.0 - rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        # dv += p_drop^T do
        dv_acc[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(do.dtype))
        ds0 = p * (dp - delta)                         # dsoftmax (no scale)
        if emit_ds:
            ds_ref[0, 0] = ds0.astype(ds_ref.dtype)
        ds = (ds0 * scale).astype(k.dtype)
        # dq for this q-tile is COMPLETE (all of K is here): write direct
        dq_ref[0, 0] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(k.dtype)).astype(dq_ref.dtype)
        # dk += ds^T q
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q.dtype))

    # every q-tile is live against the single k block (causal row 0 still
    # sees column 0), so no skipped branch exists — dq/ds are written on
    # every grid step.  ik rides as a traced 0 so the branch predicates
    # stay scalar-traced like the two-pass kernels'.
    _causal_branches(causal, iq, jnp.int32(0), block_q, block_k, kv_len,
                     tile)

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                          block_k: int, kv_len: int, num_q_blocks: int,
                          has_bias: bool, rate: float):
    q_ref, kt_ref, vt_ref, do_ref, lse_ref, delta_ref = refs[:6]
    i = 6
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    seed_ref = refs[i] if rate > 0 else None
    i += 1 if rate > 0 else 0
    dk_ref, dv_ref, dk_acc, dv_acc = refs[i:i + 4]

    b = pl.program_id(0)
    h = pl.program_id(1)
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(apply_mask):
        q = q_ref[0, 0]                                # (bq, d) input dtype
        kt = kt_ref[0, 0]                              # (d, bk)
        vt = vt_ref[0, 0]                              # (d, bk)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        p = jnp.exp(s - lse)                           # (bq, bk) f32
        if apply_mask:
            col = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = col < kv_len
            if causal:
                row = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                mask = jnp.logical_and(mask, col <= row)
            p = jnp.where(mask, p, 0.0)
        p_drop = p
        dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(vt.dtype))
        if rate > 0:
            keep = _dropout_keep(seed_ref, b, h, iq, ik, rate, p.shape)
            inv = 1.0 / (1.0 - rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        # dv += p_drop^T do
        dv_acc[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(do.dtype))
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        # dk += ds^T q
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q.dtype))

    _causal_branches(causal, iq, ik, block_q, block_k, kv_len, tile)

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, bias, seed, o, lse, g, scale: float,
                    causal: bool, block_q: int, block_k: int, rate: float,
                    interpret: bool, bias_grad: bool = True):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    has_bias = bias is not None
    block_q, block_k = _legal_blocks(block_q, block_k, Tq, Tk,
                                     interpret)
    # a non-learned mask bias skips the O(B*H*T^2) ds materialization —
    # the whole point of a flash kernel for long contexts
    want_dbias = has_bias and bias_grad
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # (B, H, Tq, 1)
    qp = _pad_to(q, 2, block_q)
    dop = _pad_to(g, 2, block_q)
    lsep = _pad_to(lse, 2, block_q)
    deltap = _pad_to(delta, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    Tq_p, Tk_p = qp.shape[2], kp.shape[2]
    n_q, n_k = Tq_p // block_q, Tk_p // block_k
    # pre-transposed K/V (one XLA copy each): every s = q@k^T and
    # dp = do@v^T inside the kernels becomes an MXU-native 'nn'
    # contraction instead of paying a per-grid-step Mosaic transpose
    ktp = jnp.swapaxes(kp, 2, 3)                      # (B, H, D, Tk_p)
    vtp = jnp.swapaxes(vp, 2, 3)

    if n_k == 1:
        # single k-block regime (every T <= block_k): ONE fused pass
        # computes dq/dk/dv — halves the backward's kernel launches,
        # q/k/v reads, and probability recomputes.  This is what moves
        # the flash-vs-XLA crossover down to BERT fine-tuning lengths
        # (VERDICT r4 directive 3).
        fused_in_specs = [
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),      # q
            pl.BlockSpec((1, 1, Tk_p, D),
                         lambda b, h, i: (b, h, 0, 0)),      # k (resident)
            pl.BlockSpec((1, 1, D, Tk_p),
                         lambda b, h, i: (b, h, 0, 0)),      # k^T (resident)
            pl.BlockSpec((1, 1, D, Tk_p),
                         lambda b, h, i: (b, h, 0, 0)),      # v^T (resident)
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),      # do
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i: (b, h, i, 0)),      # lse
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i: (b, h, i, 0)),      # delta
        ]
        fused_args = [qp, kp, ktp, vtp, dop, lsep, deltap]
        if has_bias:
            Bb, Hb, Tqb = bias.shape[0], bias.shape[1], bias.shape[2]
            bshape = ((1, 1, 1, Tk_p) if Tqb == 1
                      else (1, 1, block_q, Tk_p))
            fused_in_specs.append(pl.BlockSpec(
                bshape,
                lambda b, h, i, Bb=Bb, Hb=Hb, Tqb=Tqb: (
                    b if Bb > 1 else 0, h if Hb > 1 else 0,
                    0 if Tqb == 1 else i, 0)))
            fused_args.append(_pad_bias(bias, block_q, block_k))
        if rate > 0:
            fused_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            fused_args.append(seed)

        fused_out_specs = [
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),      # dq
            pl.BlockSpec((1, 1, Tk_p, D),
                         lambda b, h, i: (b, h, 0, 0)),      # dk
            pl.BlockSpec((1, 1, Tk_p, D),
                         lambda b, h, i: (b, h, 0, 0)),      # dv
        ]
        fused_out_shape = [
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype),
        ]
        if want_dbias:
            fused_out_specs.append(pl.BlockSpec(
                (1, 1, block_q, Tk_p), lambda b, h, i: (b, h, i, 0)))
            fused_out_shape.append(
                jax.ShapeDtypeStruct((B, H, Tq_p, Tk_p), jnp.float32))

        outs = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, kv_len=Tk,
                num_q_blocks=n_q, has_bias=has_bias, rate=rate,
                emit_ds=want_dbias),
            grid=(B, H, n_q),
            in_specs=fused_in_specs,
            out_specs=fused_out_specs,
            out_shape=fused_out_shape,
            scratch_shapes=[pltpu.VMEM((Tk_p, D), jnp.float32),   # dk acc
                            pltpu.VMEM((Tk_p, D), jnp.float32)],  # dv acc
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
        )(*fused_args)
        if want_dbias:
            dq, dk, dv, ds_full = outs
            ds_full = ds_full[:, :, :Tq, :Tk]
            red = tuple(ax for ax, size in enumerate(bias.shape[:3])
                        if size == 1)
            d_bias = (ds_full.sum(axis=red, keepdims=True) if red
                      else ds_full).astype(bias.dtype)
        else:
            dq, dk, dv = outs
            d_bias = None
        return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk], d_bias

    # two-pass path (n_k > 1): dq kernel then dkv kernel
    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j: (b, h, j, 0))
    kt_spec = pl.BlockSpec((1, 1, D, block_k),
                           lambda b, h, i, j: (b, h, 0, j))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))

    in_specs = [q_spec, k_spec, kt_spec, kt_spec, q_spec,
                row_spec, row_spec]
    args = [qp, kp, ktp, vtp, dop, lsep, deltap]
    if has_bias:
        bp = _pad_bias(bias, block_q, block_k)
        in_specs.append(_bias_spec(bias.shape, block_q, block_k))
        args.append(bp)
    if rate > 0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)

    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype)]
    if want_dbias:
        # the softmax cotangent, materialized so d_bias can reduce over
        # broadcast dims — O(B*H*T^2), the price of a LEARNED dense bias
        out_specs.append(pl.BlockSpec((1, 1, block_q, block_k),
                                      lambda b, h, i, j: (b, h, i, j)))
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, Tq_p, Tk_p), jnp.float32))

    dq_out = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          kv_len=Tk, num_k_blocks=n_k, has_bias=has_bias,
                          rate=rate, emit_ds=want_dbias),
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs if want_dbias else out_specs[0],
        out_shape=out_shape if want_dbias else out_shape[0],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_GRID_SEMANTICS,
    )(*args)
    if want_dbias:
        dq, ds_full = dq_out
    else:
        dq, ds_full = dq_out, None

    # dk/dv: swap the roles — kv blocks on the parallel axis, q blocks
    # sequential
    qs_spec = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, h, j, i: (b, h, i, 0))
    ks_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, j, i: (b, h, j, 0))
    kts_spec = pl.BlockSpec((1, 1, D, block_k),
                            lambda b, h, j, i: (b, h, 0, j))
    rows_spec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, j, i: (b, h, i, 0))
    in_specs2 = [qs_spec, kts_spec, kts_spec, qs_spec,
                 rows_spec, rows_spec]
    args2 = [qp, ktp, vtp, dop, lsep, deltap]
    if has_bias:
        in_specs2.append(_bias_spec(bias.shape, block_q, block_k,
                                    kv_major=True))
        args2.append(_pad_bias(bias, block_q, block_k))
    if rate > 0:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(seed)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          kv_len=Tk, num_q_blocks=n_q, has_bias=has_bias,
                          rate=rate),
        grid=(B, H, n_k, n_q),
        in_specs=in_specs2,
        out_specs=[ks_spec, ks_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_GRID_SEMANTICS,
    )(*args2)

    d_bias = None
    if want_dbias:
        ds_full = ds_full[:, :, :Tq, :Tk]
        # reduce over broadcast dims (incl. a Tq-broadcast row bias's
        # query axis) back to the bias shape
        red = tuple(ax for ax, size in enumerate(bias.shape[:3])
                    if size == 1)
        d_bias = ds_full.sum(axis=red, keepdims=True) if red else ds_full
        d_bias = d_bias.astype(bias.dtype)
    return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk], d_bias


def _dense_reference(q, k, v, scale: float, causal: bool, bias=None):
    """O(T^2) reference in plain XLA."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        # top-left alignment (col <= row), matching the kernel's mask
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _interpret() -> bool:
    """Interpret mode is what the CPU backend (tests) runs; on an
    accelerator backend the kernels always lower through Mosaic."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash2(q, k, v, bias, seed, rate, scale, causal, block_q, block_k,
            bias_grad=True):
    out, _ = _flash_forward(q, k, v, bias, seed, scale, causal, block_q,
                            block_k, rate, _interpret())
    return out


def _flash2_fwd(q, k, v, bias, seed, rate, scale, causal, block_q,
                block_k, bias_grad=True):
    out, lse = _flash_forward(q, k, v, bias, seed, scale, causal, block_q,
                              block_k, rate, _interpret())
    return out, (q, k, v, bias, seed, out, lse)


def _flash2_bwd(rate, scale, causal, block_q, block_k, bias_grad, res, g):
    q, k, v, bias, seed, o, lse = res
    dq, dk, dv, d_bias = _flash_backward(
        q, k, v, bias, seed, o, lse, g, scale, causal, block_q, block_k,
        rate, _interpret(), bias_grad=bias_grad)
    if d_bias is None and bias is not None:
        d_bias = jnp.zeros_like(bias)
    d_seed = None if seed is None else \
        onp.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, d_bias, d_seed


_flash2.defvjp(_flash2_fwd, _flash2_bwd)


# measured optimum on v5e (attn_probe sweep, r3; git history < PR 30): tall
# q-blocks over full-width k-blocks, clamped to T per call. Single source
# of truth — ops/transformer.py's env-var defaults read these too.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 1024


def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    bias=None, dropout: float = 0.0,
                    dropout_seed=None, bias_grad: bool = True):
    """Flash attention over (B, T, H, D) inputs (jax layout convention).

    bias: additive score bias/mask of shape (1|B, 1|H, Tq, Tk) — the two
    leading dims may broadcast, the trailing two must be full-size.
    bias_grad=False marks a non-learned mask: its gradient is skipped,
    avoiding the O(B*H*T^2) softmax-cotangent materialization.
    dropout: probability-dropout rate on the attention weights;
    dropout_seed: int32 array of shape (2,) (derive from a threefry key);
    required when dropout > 0. On CPU, dropout falls back to the dense
    XLA path (the TPU PRNG has no interpret-mode implementation).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if bias is not None and (bias.ndim != 4 or
                             bias.shape[2] not in (1, q.shape[1]) or
                             bias.shape[3] != k.shape[1]):
        raise ValueError(
            f"flash_attention bias must be (1|B, 1|H, 1|Tq, Tk); got "
            f"{bias.shape} for Tq={q.shape[1]}, Tk={k.shape[1]} — "
            "the trailing key dim must be full-size")
    # kernel blocks over (B, H, T, D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    block_q = min(block_q, max(qt.shape[2], 8))
    block_k = min(block_k, max(kt.shape[2], 8))
    rate = float(dropout)
    if rate > 0 and dropout_seed is None:
        raise ValueError("flash_attention: dropout > 0 needs dropout_seed")
    if rate > 0 and _interpret():
        # dense differentiable fallback with jax-level dropout — same
        # platform decision as the kernels (the TPU PRNG has no
        # interpret-mode implementation)
        out = dense_dropout_attention_bhtd(
            qt, kt, vt, bias, jnp.asarray(dropout_seed, jnp.int32), rate,
            float(scale), bool(causal))
        return jnp.swapaxes(out, 1, 2)
    seed = None if rate == 0 else jnp.asarray(dropout_seed, jnp.int32)
    out = _flash2(qt, kt, vt, bias, seed, rate, float(scale), bool(causal),
                  int(block_q), int(block_k), bool(bias_grad))
    return jnp.swapaxes(out, 1, 2)


def dense_dropout_attention_bhtd(q, k, v, bias, seed, rate, scale, causal):
    """Plain-XLA attention with probability dropout over (B, H, T, D)
    operands — the shared differentiable fallback for platforms/paths
    without the Pallas kernel. ``seed`` is a (2,) int32 array."""
    key = jax.random.wrap_key_data(
        jnp.asarray(seed, jnp.uint32).reshape(2,), impl="threefry2x32")
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        m = jnp.tril(jnp.ones((Tq, Tk), bool))
        s = jnp.where(m, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    keep = jax.random.bernoulli(key, 1.0 - rate, p.shape)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
