"""Flash attention — blockwise online-softmax Pallas kernel.

Reference parity (leezu/mxnet): the reference's attention is full O(T²)
materialized scores (``src/operator/contrib/transformer.cu``); this kernel
is the TPU-native upgrade (SURVEY.md 5.7): tiles of Q stream over tiles of
K/V held in VMEM with a running max/denominator, so scores never hit HBM.

Layout (PR 36): every kernel indexes the projections' own (B, T, H·D)
arrays — grid (B, H/g, Tq-blocks[, Tk-blocks]), a block the ``g·D`` lanes
of one head group (``head_group``: two 64-wide heads, one 128-wide) — and
writes o, dq, dk, dv the same way, so no transposed copy stands on either
side of a call; ``lse`` is kept lanes-dense, a row a head.  The (B, H, T, D)
form it replaced was stored padded on the chip: a 64-wide minor dimension
to 128 lanes (twice the bytes) and the (B, H, T, 1) statistics 128-fold
(the parent's HLO, PR 36: PERF.md section 6).

Forward accumulates sequentially over the last grid axis in VMEM scratch,
emitting the per-row log-sum-exp. Backward is blockwise too (standard
flash-attention recipe): a dq kernel streams K/V blocks against the saved
LSE and ``delta = rowsum(dO·O)`` (taken inside the kernels from the dO and
O blocks they hold), and a dk/dv kernel streams Q/dO blocks — scores are
recomputed per tile and never hit HBM in either direction.  Where K fits
one block (every T <= 1024) one fused kernel does both.

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# every kernel accumulates over its LAST grid axis only; telling Mosaic
# the rest are parallel lets it pipeline/reorder grid steps
_LAST_AXIS_CARRIES = ("parallel", "parallel", "parallel", "arbitrary")
# the two-pass backward at float32 (256 x 1024 scores for each of two
# heads beside K, V and the block-sized accumulators) passes Mosaic's
# default 16 MiB of scoped VMEM by 2 MiB; a v5e core has 128 MiB.  Only
# those kernels ask for more: what a Mosaic call reserves, XLA's
# memory-space assignment loses for the whole program around it
_VMEM_LIMIT = 32 * 1024 * 1024


def _compiler_params(semantics, vmem_limit_bytes=None):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def _prec(dtype):
    """Explicit dot precision per operand dtype: the kernel's contract is
    bf16 MXU passes for low-precision inputs and exact fp32 for f32 —
    INDEPENDENT of the global jax_default_matmul_precision (a global
    'highest' would otherwise request an fp32 contract on bf16 operands,
    which Mosaic rejects with 'Bad lhs type')."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


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


class _Cfg(NamedTuple):
    """What every kernel of this file is specialised on."""
    scale: float
    causal: bool
    block_q: int
    block_k: int
    kv_len: int
    rate: float
    g: int                  # heads a grid step
    D: int                  # head width
    bias_heads: bool        # the bias has a row a head (else one for all)


def _causal_branches(c: _Cfg, iq, ik, tile, skipped=None):
    """Dispatch one grid step to the right specialization of ``tile``:

    - fully-masked tiles (above the causal diagonal) execute NOTHING —
      at T=1024/128-blocks this halves the kernel's matmul work, the
      reason a causal flash kernel can beat XLA's full-T² attention;
    - interior tiles (fully below the diagonal, inside kv range) skip
      the iota/compare/where masking entirely;
    - only diagonal-straddling or kv-padded tiles pay the masked path.
    All conditions are scalar functions of the grid ids, so Mosaic
    executes exactly one branch per step."""
    need_kv = (ik + 1) * c.block_k > c.kv_len
    if c.causal:
        live = ik * c.block_k <= (iq + 1) * c.block_q - 1
        need_mask = jnp.logical_or(
            (ik + 1) * c.block_k - 1 > iq * c.block_q, need_kv)

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


def _split_refs(refs, n_in, has_bias, rate):
    """(the ``n_in`` fixed inputs, bias ref, seed ref, outputs + scratch)."""
    i = n_in + int(has_bias)
    return (refs[:n_in], refs[n_in] if has_bias else None,
            refs[i] if rate > 0 else None, refs[i + int(rate > 0):])


def _head_mask(shape, h, D):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.logical_and(lane >= h * D, lane < (h + 1) * D)


def _only_head(x, h, c):
    """``x`` (rows, g·D) with every head's lanes but ``h``'s zeroed.  A
    matmul that contracts the block's lanes then sees head ``h`` alone,
    and one that produces a block fills only that head's lanes: no lane
    slice, no shift.  The MXU is 128 deep and 128 wide, so a 64-wide head
    alone would cost the same passes the zero-padded pair does."""
    if c.g == 1:
        return x
    return jnp.where(_head_mask(x.shape, h, c.D), x, jnp.zeros_like(x))


def _put_head(block, x, h, c):
    """``block`` with head ``h``'s lanes taken from ``x``."""
    if c.g == 1:
        return x
    if block is None:
        return _only_head(x, h, c)
    return jnp.where(_head_mask(x.shape, h, c.D), x, block)


def _tile_mask(shape, iq, ik, c):
    col = ik * c.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = col < c.kv_len
    if c.causal:
        row = iq * c.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = jnp.logical_and(mask, col <= row)
    return mask


def _cols_to_rows(cols, n_rows):
    """Per-head row statistics, each a (n, 1) column as the softmax
    leaves it, as the (n_rows, n) lanes-dense rows they are stored in:
    head ``h`` in row ``h``.  A (…, T, 1) float32 array is tiled to 128
    lanes in HBM, 128 x its bytes (the parent's HLO, PR 36); this costs
    one (n, 128) transpose a grid step instead."""
    n = cols[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
    wide = jnp.zeros((n, _LANES), jnp.float32)
    for h, col in enumerate(cols):
        wide = jnp.where(lane == h, col, wide)
    return wide.T[:n_rows]


def _rows_to_cols(rows, g):
    """Inverse of :func:`_cols_to_rows`: the first ``g`` rows as (n, 1)
    columns."""
    n_rows, n = rows.shape
    tall = jnp.concatenate(
        [rows, jnp.zeros((_LANES - n_rows, n), rows.dtype)], axis=0)
    wide = tall.T
    return [wide[:, h:h + 1] for h in range(g)]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_prec(a.dtype))


_NN = ((1,), (0,))          # a @ b
_NT = ((1,), (1,))          # a @ b^T
_TN = ((0,), (0,))          # a^T @ b


def _scores(qm, k, bias_ref, h, c):
    s = _dot(qm, k, _NT) * c.scale
    if bias_ref is not None:
        s = s + bias_ref[0, h if c.bias_heads else 0].astype(jnp.float32)
    return s


def _flash_fwd_kernel(*refs, c: _Cfg, num_k_blocks: int, has_bias: bool):
    (q_ref, k_ref, v_ref), bias_ref, seed_ref, rest = _split_refs(
        refs, 3, has_bias, c.rate)
    o_ref, lse_ref = rest[:2]
    n_rows = lse_ref.shape[2]

    b = pl.program_id(0)
    hg = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    def probs(h, q, k, apply_mask, m_prev=None):
        """(running row max, exp(s - max) after dropout, its row sum
        before) for head ``h`` of the group against this K tile."""
        s = _scores(_only_head(q, h, c), k, bias_ref, h, c)
        if apply_mask:
            s = jnp.where(_tile_mask(s.shape, iq, ik, c), s, _NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        if m_prev is not None:
            m = jnp.maximum(m_prev, m)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if c.rate > 0:
            keep = _dropout_keep(seed_ref, b, hg * c.g + h, iq, ik,
                                 c.rate, p.shape)
            p = jnp.where(keep, p / (1.0 - c.rate), 0.0)
        return m, p, l

    if num_k_blocks == 1:
        # single-block specialization (every T <= block_k): the whole K
        # is in this tile, so the online-softmax carry (acc rescale,
        # running m/l scratch reads/writes) is pure overhead — a plain
        # row softmax computes the exact same result ~15% faster.
        def tile1(apply_mask):
            q, k, v = q_ref[0], k_ref[0], v_ref[0]    # (bq|Tk, g·D)
            out, lse = None, []
            for h in range(c.g):
                m, p, l = probs(h, q, k, apply_mask)
                denom = jnp.maximum(l, 1e-30)
                acc = _dot(p.astype(v.dtype), v, _NN)
                out = _put_head(out, acc * (1.0 / denom), h, c)
                lse.append(m + jnp.log(denom))
            o_ref[0] = out.astype(o_ref.dtype)
            lse_ref[0, 0] = _cols_to_rows(lse, n_rows)

        _causal_branches(c, iq, ik, tile1)
        return

    acc_ref, m_ref, l_ref = rest[2:5]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(apply_mask):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        acc = acc_ref[...]
        for h in range(c.g):
            m_prev = m_ref[h]                         # (bq, 1)
            m_new, p, l = probs(h, q, k, apply_mask, m_prev)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + l
            m_ref[h] = m_new
            acc = _put_head(
                acc, acc * alpha + _dot(p.astype(v.dtype), v, _NN), h, c)
        acc_ref[...] = acc

    _causal_branches(c, iq, ik, tile)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        out, lse = None, []
        for h in range(c.g):
            denom = jnp.maximum(l_ref[h], 1e-30)
            out = _put_head(out, acc_ref[...] * (1.0 / denom), h, c)
            lse.append(m_ref[h] + jnp.log(denom))
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0, 0] = _cols_to_rows(lse, n_rows)


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
    or whole.  ``block_k`` is the lane dim of the bias blocks and of the
    score tiles, ``block_q`` the lane dim of the statistics' rows, and
    Mosaic transposes whole (8, 128) tiles: a block that is no multiple
    of 128 becomes the whole axis, padded up to one (same math, one
    block).  Interpret mode (CPU) keeps the requested blocks for
    multi-block coverage."""
    if not interpret:
        if block_k % _LANES:
            block_k = -(-Tk // _LANES) * _LANES
        if block_q % _LANES:
            block_q = -(-Tq // _LANES) * _LANES
    return block_q, block_k


def _pad_bias(bias, block_q, block_k):
    if bias.shape[2] == 1:          # Tq-broadcast row bias: pad Tk only
        return _pad_to(bias, 3, block_k)
    return _pad_to(_pad_to(bias, 2, block_q), 3, block_k)


class _Specs(NamedTuple):
    q: pl.BlockSpec         # q, o, do, dq: (1, block_q, g·D) of (B, Tq, H·D)
    k: pl.BlockSpec         # k, v, dk, dv: (1, block_k, g·D) of (B, Tk, H·D)
    stat: pl.BlockSpec      # lse: (1, 1, rows, block_q) of (B, H/g, rows, Tq)
    ds: pl.BlockSpec        # (1, g, block_q, block_k) of (B, H, Tq, Tk)
    bias: Optional[pl.BlockSpec]


def _specs(ids, c: _Cfg, n_rows, bias_shape):
    """Block specs of every kind of operand under a grid whose indices
    ``ids`` maps to (batch, head group, q block, k block).  The head
    group picks ``g·D`` LANES of the projections' own (B, T, H·D) array:
    no (B, H, T, D) copy exists on either side of the kernels."""
    def at(f):
        return lambda *grid: f(*ids(*grid))

    bias = None
    if bias_shape is not None:      # (1|B, 1|H, 1|Tq, Tk): streams like K
        Bb, Hb, Tqb = bias_shape[:3]
        bias = pl.BlockSpec(
            (1, c.g if Hb > 1 else 1, 1 if Tqb == 1 else c.block_q,
             c.block_k),
            at(lambda b, h, i, j: (b if Bb > 1 else 0, h if Hb > 1 else 0,
                                   0 if Tqb == 1 else i, j)))
    lanes = c.g * c.D
    return _Specs(
        q=pl.BlockSpec((1, c.block_q, lanes),
                       at(lambda b, h, i, j: (b, i, h))),
        k=pl.BlockSpec((1, c.block_k, lanes),
                       at(lambda b, h, i, j: (b, j, h))),
        stat=pl.BlockSpec((1, 1, n_rows, c.block_q),
                          at(lambda b, h, i, j: (b, h, 0, i))),
        ds=pl.BlockSpec((1, c.g, c.block_q, c.block_k),
                        at(lambda b, h, i, j: (b, h, i, j))),
        bias=bias)


def _bias_and_seed(sp: _Specs, bias, seed, c: _Cfg):
    """(specs, operands) of the two optional inputs, in the order
    :func:`_split_refs` takes them apart."""
    specs, args = [], []
    if bias is not None:
        specs.append(sp.bias)
        args.append(_pad_bias(bias, c.block_q, c.block_k))
    if c.rate > 0:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    return specs, args


def head_group(num_heads: int, head_dim: int) -> int:
    """Heads a grid step: the fewest whose lanes fill whole 128-lane
    tiles (2 at a head width of 64, 1 at 128), else every head — the
    whole last dimension is always a legal block."""
    for g in range(1, num_heads):
        if num_heads % g == 0 and (g * head_dim) % _LANES == 0:
            return g
    return num_heads


def _plan(q, k, bias, scale, causal, block_q, block_k, rate, interpret,
          num_heads):
    """(_Cfg, rows of the statistics' blocks, padded Tq, padded Tk)."""
    Tq, Tk, D = q.shape[1], k.shape[1], q.shape[2] // num_heads
    g = head_group(num_heads, D)
    if g > _LANES:
        raise ValueError(
            f"flash attention: {num_heads} heads of {D} form no group of "
            f"at most {_LANES} heads")
    block_q, block_k = _legal_blocks(block_q, block_k, Tq, Tk, interpret)
    c = _Cfg(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
             kv_len=Tk, rate=rate, g=g, D=D,
             bias_heads=bias is not None and bias.shape[1] > 1)
    return (c, -(-g // 8) * 8, Tq + (-Tq) % block_q, Tk + (-Tk) % block_k)


def _flash_forward(q, k, v, bias, seed, scale: float, causal: bool,
                   block_q: int, block_k: int, rate: float,
                   interpret: bool, num_heads: int):
    """q (B, Tq, H·D), k/v (B, Tk, H·D), as the projections leave them.
    Returns (o (B, Tq, H·D), lse): ``lse`` is float32
    (B, H/g, rows >= g, Tq padded to the block), head ``h`` of a group in
    its row ``h``, handed to :func:`_flash_backward` as it is
    (:func:`lse_weights` is the (B, Tq, H) view of it)."""
    B, Tq, C = q.shape
    c, n_rows, Tq_p, Tk_p = _plan(q, k, bias, scale, causal, block_q,
                                  block_k, rate, interpret, num_heads)
    n_q, n_k = Tq_p // c.block_q, Tk_p // c.block_k
    sp = _specs(lambda b, h, i, j: (b, h, i, j), c, n_rows,
                None if bias is None else bias.shape)
    more_specs, more_args = _bias_and_seed(sp, bias, seed, c)
    in_specs = [sp.q, sp.k, sp.k] + more_specs
    args = [_pad_to(q, 1, c.block_q), _pad_to(k, 1, c.block_k),
            _pad_to(v, 1, c.block_k)] + more_args

    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, c=c, num_k_blocks=n_k,
                          has_bias=bias is not None),
        grid=(B, num_heads // c.g, n_q, n_k),
        in_specs=in_specs,
        out_specs=[sp.q, sp.stat],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tq_p, C), q.dtype),
            jax.ShapeDtypeStruct((B, num_heads // c.g, n_rows, Tq_p),
                                 jnp.float32),
        ],
        # the single-block specialization needs no online-softmax carry —
        # don't reserve VMEM it never touches
        scratch_shapes=([] if n_k == 1 else [
            pltpu.VMEM((c.block_q, c.g * c.D), jnp.float32),      # acc
            pltpu.VMEM((c.g, c.block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((c.g, c.block_q, 1), jnp.float32),     # running denom
        ]),
        interpret=interpret,
        compiler_params=_compiler_params(_LAST_AXIS_CARRIES),
    )(*args)
    return out[:, :Tq], lse


def lse_weights(lse, num_heads: int, Tq: int):
    """The kernels' ``lse`` (or anything in its layout) as (B, Tq, H, 1),
    to weigh (B, Tq, H, D) outputs by."""
    B, G, _, _ = lse.shape
    rows = lse[:, :, :num_heads // G, :Tq].reshape(B, num_heads, Tq)
    return jnp.swapaxes(rows, 1, 2)[..., None]


def _bwd_heads(q, k, v, do, o, lse_rows, bias_ref, seed_ref, ids,
               apply_mask, c: _Cfg):
    """What every backward kernel recomputes for one (q block, k block)
    tile, head by head of the group: yields (h, q and dO with only head
    h's lanes, the probabilities after dropout, the softmax cotangent
    before the scale).  ``delta = rowsum(dO·O)`` is taken here from the
    blocks the kernel holds."""
    b, hg, iq, ik = ids
    lse = _rows_to_cols(lse_rows, c.g)
    dod = do.astype(jnp.float32) * o.astype(jnp.float32)
    mask = None
    if apply_mask:
        mask = _tile_mask((q.shape[0], k.shape[0]), iq, ik, c)
    for h in range(c.g):
        qm, dom = _only_head(q, h, c), _only_head(do, h, c)
        delta = jnp.sum(_only_head(dod, h, c), axis=1, keepdims=True)
        p = jnp.exp(_scores(qm, k, bias_ref, h, c) - lse[h])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot(dom, v, _NT)
        p_drop = p
        if c.rate > 0:
            keep = _dropout_keep(seed_ref, b, hg * c.g + h, iq, ik,
                                 c.rate, p.shape)
            inv = 1.0 / (1.0 - c.rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        yield h, qm, dom, p_drop, p * (dp - delta)


def _sum(total, x):
    return x if total is None else total + x


def _flash_bwd_fused_kernel(*refs, c: _Cfg, num_q_blocks: int,
                            has_bias: bool, emit_ds: bool):
    """Single-pass backward for the n_k == 1 regime (Tk fits one k-block
    — every T <= block_k, i.e. all BERT/GPT headline shapes under the
    default 1024 block).  The two-pass recipe pays two kernel launches
    that each re-read q/k/v and re-compute the probabilities; here one
    grid (B, H/g, n_q) computes s and p ONCE per q-tile, emits dq directly
    (the whole K is resident, so dq needs no cross-block accumulation),
    and accumulates dk/dv in VMEM scratch over the sequential q axis.
    K/V block specs are constant in iq, so Mosaic keeps them in VMEM
    across the whole (b, group) pass — q/k/v stream exactly once."""
    (q_ref, k_ref, v_ref, do_ref, o_ref,
     lse_ref), bias_ref, seed_ref, rest = _split_refs(refs, 6, has_bias,
                                                      c.rate)
    dq_ref, dk_ref, dv_ref = rest[:3]
    ds_ref = rest[3] if emit_ds else None
    acc = rest[-2:] if num_q_blocks > 1 else None

    ids = (pl.program_id(0), pl.program_id(1), pl.program_id(2), 0)
    iq = ids[2]

    if acc:
        @pl.when(iq == 0)
        def _init():
            for ref in acc:
                ref[...] = jnp.zeros_like(ref)

    def tile(apply_mask):
        k, do = k_ref[0], do_ref[0]
        dq = dk = dv = None
        for h, qm, dom, p_drop, ds0 in _bwd_heads(
                q_ref[0], k, v_ref[0], do, o_ref[0], lse_ref[0, 0],
                bias_ref, seed_ref, ids, apply_mask, c):
            if emit_ds:
                ds_ref[0, h] = ds0
            ds = (ds0 * c.scale).astype(k.dtype)
            # dq for this q-tile is COMPLETE (all of K is here)
            dq = _put_head(dq, _dot(ds, k, _NN), h, c)
            dv = _sum(dv, _dot(p_drop.astype(do.dtype), dom, _TN))
            dk = _sum(dk, _dot(ds, qm, _TN))
        dq_ref[0] = dq.astype(dq_ref.dtype)
        if acc:
            acc[0][...] += dk
            acc[1][...] += dv
        else:
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)

    # every q-tile is live against the single k block (causal row 0 still
    # sees column 0), so no skipped branch exists — dq/ds are written on
    # every grid step.  ik rides as a traced 0 so the branch predicates
    # stay scalar-traced like the two-pass kernels'.
    _causal_branches(c, iq, jnp.int32(0), tile)

    if acc:
        @pl.when(iq == num_q_blocks - 1)
        def _finish():
            dk_ref[0] = acc[0][...].astype(dk_ref.dtype)
            dv_ref[0] = acc[1][...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(*refs, c: _Cfg, num_k_blocks: int, has_bias: bool,
                         emit_ds: bool):
    (q_ref, k_ref, v_ref, do_ref, o_ref,
     lse_ref), bias_ref, seed_ref, rest = _split_refs(refs, 6, has_bias,
                                                      c.rate)
    dq_ref, dq_acc = rest[0], rest[-1]
    ds_ref = rest[1] if emit_ds else None

    ids = tuple(pl.program_id(i) for i in range(4))
    iq, ik = ids[2], ids[3]

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(apply_mask):
        k = k_ref[0]
        dq = dq_acc[...]
        for h, _, _, _, ds0 in _bwd_heads(
                q_ref[0], k, v_ref[0], do_ref[0], o_ref[0], lse_ref[0, 0],
                bias_ref, seed_ref, ids, apply_mask, c):
            if emit_ds:
                ds_ref[0, h] = ds0
            ds = (ds0 * c.scale).astype(k.dtype)
            dq = _put_head(dq, dq + _dot(ds, k, _NN), h, c)
        dq_acc[...] = dq

    def skipped():
        ds_ref[0] = jnp.zeros_like(ds_ref[0])

    _causal_branches(c, iq, ik, tile,
                     skipped=skipped if emit_ds else None)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, c: _Cfg, num_q_blocks: int,
                          has_bias: bool):
    (q_ref, k_ref, v_ref, do_ref, o_ref,
     lse_ref), bias_ref, seed_ref, rest = _split_refs(refs, 6, has_bias,
                                                      c.rate)
    dk_ref, dv_ref, dk_acc, dv_acc = rest

    b, hg, ik, iq = (pl.program_id(i) for i in range(4))

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(apply_mask):
        do = do_ref[0]
        dk = dv = None
        for _, qm, dom, p_drop, ds0 in _bwd_heads(
                q_ref[0], k_ref[0], v_ref[0], do, o_ref[0], lse_ref[0, 0],
                bias_ref, seed_ref, (b, hg, iq, ik), apply_mask, c):
            dv = _sum(dv, _dot(p_drop.astype(do.dtype), dom, _TN))
            dk = _sum(dk, _dot((ds0 * c.scale).astype(do.dtype), qm, _TN))
        dk_acc[...] += dk
        dv_acc[...] += dv

    _causal_branches(c, iq, ik, tile)

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, bias, seed, o, lse, do, scale: float,
                    causal: bool, block_q: int, block_k: int, rate: float,
                    interpret: bool, num_heads: int,
                    bias_grad: bool = True):
    """Gradients (dq, dk, dv, d_bias) in the operands' own layouts;
    ``lse`` as :func:`_flash_forward` returned it under the same blocks."""
    B, Tq, C = q.shape
    Tk = k.shape[1]
    c, n_rows, Tq_p, Tk_p = _plan(q, k, bias, scale, causal, block_q,
                                  block_k, rate, interpret, num_heads)
    n_q, n_k = Tq_p // c.block_q, Tk_p // c.block_k
    G, lanes = num_heads // c.g, c.g * c.D
    has_bias = bias is not None
    # a non-learned mask bias skips the O(B*H*T^2) ds materialization —
    # the whole point of a flash kernel for long contexts
    want_dbias = has_bias and bias_grad

    def call(kernel, ids, grid, outs, scratch, semantics, **kw):
        sp = _specs(ids, c, n_rows, bias.shape if has_bias else None)
        more_specs, more_args = _bias_and_seed(sp, bias, seed, c)
        in_specs = [sp.q, sp.k, sp.k, sp.q, sp.q, sp.stat] + more_specs
        args = [_pad_to(q, 1, c.block_q), _pad_to(k, 1, c.block_k),
                _pad_to(v, 1, c.block_k), _pad_to(do, 1, c.block_q),
                _pad_to(o, 1, c.block_q), lse] + more_args
        shapes = dict(
            q=jax.ShapeDtypeStruct((B, Tq_p, C), q.dtype),
            k=jax.ShapeDtypeStruct((B, Tk_p, C), k.dtype),
            # the softmax cotangent, materialized so d_bias can reduce
            # over broadcast dims — O(B*H*T^2), the price of a LEARNED
            # dense bias
            ds=jax.ShapeDtypeStruct((B, num_heads, Tq_p, Tk_p),
                                    jnp.float32))
        return pl.pallas_call(
            functools.partial(kernel, c=c, has_bias=has_bias, **kw),
            grid=grid, in_specs=in_specs,
            out_specs=[getattr(sp, o_) for o_ in outs],
            out_shape=[shapes[o_] for o_ in outs],
            scratch_shapes=[pltpu.VMEM((rows, lanes), jnp.float32)
                            for rows in scratch],
            interpret=interpret,
            compiler_params=_compiler_params(
                semantics, _VMEM_LIMIT if n_k > 1 else None),
        )(*args)

    ds_full = None
    if n_k == 1:
        # single k-block regime (every T <= block_k): ONE fused pass
        # computes dq/dk/dv — halves the backward's kernel launches,
        # q/k/v reads, and probability recomputes.  This is what moves
        # the flash-vs-XLA crossover down to BERT fine-tuning lengths
        # (VERDICT r4 directive 3).
        outs = call(
            _flash_bwd_fused_kernel, lambda b, h, i: (b, h, i, 0),
            (B, G, n_q), ["q", "k", "k"] + ["ds"] * want_dbias,
            [Tk_p, Tk_p] if n_q > 1 else [],
            ("parallel", "parallel", "arbitrary"),
            num_q_blocks=n_q, emit_ds=want_dbias)
        dq, dk, dv = outs[:3]
        if want_dbias:
            ds_full = outs[3]
    else:
        # two-pass path (n_k > 1): dq kernel, then dk/dv with the roles
        # swapped — kv blocks on the parallel axis, q blocks sequential
        outs = call(
            _flash_bwd_dq_kernel, lambda b, h, i, j: (b, h, i, j),
            (B, G, n_q, n_k), ["q"] + ["ds"] * want_dbias, [c.block_q],
            _LAST_AXIS_CARRIES,
            num_k_blocks=n_k, emit_ds=want_dbias)
        dq = outs[0]
        if want_dbias:
            ds_full = outs[1]
        dk, dv = call(
            _flash_bwd_dkv_kernel, lambda b, h, j, i: (b, h, i, j),
            (B, G, n_k, n_q), ["k", "k"], [c.block_k, c.block_k],
            _LAST_AXIS_CARRIES, num_q_blocks=n_q)

    d_bias = None
    if want_dbias:
        ds_full = ds_full[:, :, :Tq, :Tk]
        # reduce over broadcast dims (incl. a Tq-broadcast row bias's
        # query axis) back to the bias shape
        red = tuple(ax for ax, size in enumerate(bias.shape[:3])
                    if size == 1)
        d_bias = ds_full.sum(axis=red, keepdims=True) if red else ds_full
        d_bias = d_bias.astype(bias.dtype)
    return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk], d_bias


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


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash2(q, k, v, bias, seed, rate, scale, causal, block_q, block_k,
            bias_grad, num_heads):
    """The kernels over (B, T, H·D) operands with their gradients."""
    out, _ = _flash_forward(q, k, v, bias, seed, scale, causal, block_q,
                            block_k, rate, _interpret(), num_heads)
    return out


def _flash2_fwd(q, k, v, bias, seed, rate, scale, causal, block_q,
                block_k, bias_grad, num_heads):
    out, lse = _flash_forward(q, k, v, bias, seed, scale, causal, block_q,
                              block_k, rate, _interpret(), num_heads)
    return out, (q, k, v, bias, seed, out, lse)


def _flash2_bwd(rate, scale, causal, block_q, block_k, bias_grad,
                num_heads, res, g):
    q, k, v, bias, seed, o, lse = res
    dq, dk, dv, d_bias = _flash_backward(
        q, k, v, bias, seed, o, lse, g, scale, causal, block_q, block_k,
        rate, _interpret(), num_heads, bias_grad=bias_grad)
    if d_bias is None and bias is not None:
        d_bias = jnp.zeros_like(bias)
    d_seed = None if seed is None else \
        onp.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, d_bias, d_seed


_flash2.defvjp(_flash2_fwd, _flash2_bwd)


# tall q-blocks over full-width k-blocks, clamped to T per call.  Measured
# again on the (B, T, H·D) kernels (my chip runs, PR 36, v5e, ms a layer
# with what surrounds the call): BERT b16 T512 bf16 forward + backward
# 0.890 with one 512-row q block (ops/transformer.py asks for the whole
# T up to 512), 0.998 at 256, 1.316 at 128; GPT-2 b1 T1024 float32
# causal forward 0.383 at 256, 0.397 at 512, 0.403 at 128, 0.434 at
# 1024; head width 128 (b1 T1024 bf16 causal) 0.097 at 256, 0.093 at 512.
# Contracting q and K over their last dimensions beat a K^T made in the
# kernel (0.890 against 0.939, 0.285 against 0.305 forward alone), so no
# transposed copy is kept anywhere, in HBM or in VMEM.  Single source of
# truth — ops/transformer.py's env-var defaults read these too.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 1024


def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    bias=None, dropout: float = 0.0,
                    dropout_seed=None, bias_grad: bool = True):
    """Flash attention over (B, T, H, D) inputs (jax layout convention).

    The kernels index the (B, T, H·D) array those are a free reshape of
    — a projection's output as it is — and write their outputs the same
    way: no transposed copy is made on either side.

    bias: additive score bias/mask of shape (1|B, 1|H, Tq, Tk) — the two
    leading dims may broadcast, the trailing two must be full-size.
    bias_grad=False marks a non-learned mask: its gradient is skipped,
    avoiding the O(B*H*T^2) softmax-cotangent materialization.
    dropout: probability-dropout rate on the attention weights;
    dropout_seed: int32 array of shape (2,) (derive from a threefry key);
    required when dropout > 0. On CPU, dropout falls back to the dense
    XLA path (the TPU PRNG has no interpret-mode implementation).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if bias is not None and (bias.ndim != 4 or
                             bias.shape[2] not in (1, Tq) or
                             bias.shape[3] != Tk):
        raise ValueError(
            f"flash_attention bias must be (1|B, 1|H, 1|Tq, Tk); got "
            f"{bias.shape} for Tq={Tq}, Tk={Tk} — "
            "the trailing key dim must be full-size")
    block_q = min(block_q, max(Tq, 8))
    block_k = min(block_k, max(Tk, 8))
    rate = float(dropout)
    if rate > 0 and dropout_seed is None:
        raise ValueError("flash_attention: dropout > 0 needs dropout_seed")
    if rate > 0 and _interpret():
        # dense differentiable fallback with jax-level dropout — same
        # platform decision as the kernels (the TPU PRNG has no
        # interpret-mode implementation)
        out = dense_dropout_attention_bhtd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), bias,
            jnp.asarray(dropout_seed, jnp.int32), rate, float(scale),
            bool(causal))
        return jnp.swapaxes(out, 1, 2)
    seed = None if rate == 0 else jnp.asarray(dropout_seed, jnp.int32)
    out = _flash2(q.reshape(B, Tq, H * D), k.reshape(B, Tk, H * D),
                  v.reshape(B, Tk, H * D), bias, seed, rate, float(scale),
                  bool(causal), int(block_q), int(block_k), bool(bias_grad),
                  H)
    return out.reshape(B, Tq, H, D)


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
