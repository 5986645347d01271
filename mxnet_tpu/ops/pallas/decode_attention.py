"""Ragged decode attention — one query a slot over that slot's LIVE rows.

The decode step of a served model attends, for every slot ``s``, from
the one new token to the positions ``0 .. pos[s]`` of that slot's rows
in the cache.  Dense code reads the whole capacity bucket ``L`` for
every slot; this kernel reads, for slot ``s``, the position blocks
``0 .. pos[s] // B`` and nothing past them.

Operands are the cache's own layout (``serving.kv_cache``): rows
``(S, channels, L)``, positions last, so a block of ``B`` positions
over all channels is one strided DMA and the cache is neither copied
nor relaid on the way in.  ``pos (S,)`` is scalar-prefetched and the
K/V index maps read it: of a slot's ``L / B`` grid steps the LAST
``pos[s] // B + 1`` walk its live blocks and the ones before them idle
on block 0.  A grid step whose block index did not change fetches
nothing, so an idle step costs a grid step and no bytes; and because
the idle steps come first, every fetch (a slot's block 0 included) is
issued under the live step before it and none is waited for.  Inside
the last live block the columns past ``pos[s]`` are masked, in K's
scores and in V itself: what lies there (a finished request's rows,
nothing yet) never reaches the result.

The mathematics is dense attention's: float32 scores, an online softmax
in float32 (running max, sum and accumulator in VMEM), probabilities
rounded to the rows' dtype into the V product, float32 accumulation.

Channels are split in ``G`` groups of ``C``; group ``n``'s ``R``
queries ``q[s, n] (R, C)`` meet channels ``n C .. (n + 1) C`` of K and
of V.  A family packs its heads into that shape (:func:`paired_queries`
is the differential-attention family's packing).  On the CPU the kernel
runs in interpret mode (``attention._interpret``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

# positions a block.  Fixed from chip measurements (PERF.md section 6,
# PR 31); a bucket shorter than this is read as one block.
ROW_BLOCK = 512

_SUBLANES = 8


def row_block(length: int) -> int:
    """Positions a block of a cache whose rows are ``length`` long."""
    return min(ROW_BLOCK, int(length))


def blocks_read(pos, length: int):
    """``(read, all)``: the position blocks a call at per-slot positions
    ``pos`` (a host vector) fetches, and those a dense read of every
    slot's ``length`` rows would."""
    block = row_block(length)
    return (int((pos // block + 1).sum()),
            int(pos.shape[0]) * (int(length) // block))


def _kernel(*refs, block, scale):
    # refs: pos, the entry where the rows are stacked, then q, k, v, o
    # and the scratch
    pos_ref = refs[0]
    q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs[-7:]
    s, i = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[s]
    final = pl.num_programs(1) - 1
    # the block this step walks; negative on the slot's idle steps
    j = i - (final - pos // block)
    G, R, C = acc_sc.shape
    prec = _attention._prec(k_ref.dtype)

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _attention._NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _block(masked):
        if masked:
            col = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            seen = col <= pos
        for n in range(G):
            kn = k_ref[0, n * C:(n + 1) * C, :]
            vn = v_ref[0, n * C:(n + 1) * C, :]
            sc = jax.lax.dot_general(
                q_ref[0, n], kn, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * scale
            if masked:
                sc = jnp.where(seen, sc, _attention._NEG_INF)
                vn = jnp.where(seen, vn, jnp.zeros_like(vn))
            m_prev = m_sc[n]
            m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_sc[n] = alpha * l_sc[n] + p.sum(axis=-1, keepdims=True)
            acc_sc[n] = alpha * acc_sc[n] + jax.lax.dot_general(
                p.astype(vn.dtype), vn, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            m_sc[n] = m_new

    @pl.when(jnp.logical_and(j >= 0, i < final))
    def _interior():
        _block(False)

    @pl.when(i == final)
    def _last():
        _block(True)
        o_ref[0] = acc_sc[...] / l_sc[...]


def ragged_attention(q, k_rows, v_rows, pos, scale: float, entry=None):
    """``softmax(scale q K[:, :pos + 1]) V[:, :pos + 1]`` a slot and a
    group: ``q (S, G, R, C)``, ``k_rows`` / ``v_rows (S, G C, L)``,
    ``pos (S,)`` int32 (a free slot rides at 0).  Returns
    ``(S, G, R, C)`` float32.

    With ``entry`` (an int32 scalar, traced) the rows are STACKED,
    ``(E, S, G C, L)``, and entry ``entry`` of them is read: it is
    scalar-prefetched beside ``pos`` and picks the leading block index,
    so a caller inside a loop hands the whole stack through and no
    entry of it is sliced out (a copy of ``S G C L`` elements a call)."""
    S, G, R, C = q.shape
    L = k_rows.shape[-1]
    lead = () if entry is None else (None,)
    if k_rows.ndim != 3 + len(lead):
        raise ValueError(
            f"rows {k_rows.shape}: (S, channels, L), or stacked "
            "(E, S, channels, L) with the entry to read")
    block = row_block(L)
    if L % block:
        raise ValueError(f"rows of {L} positions are not whole blocks "
                         f"of {block}")
    pad = -R % _SUBLANES
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Rp = R + pad
    steps = L // block

    def rows_at(s, i, pos_ref, *entry_ref):
        return tuple(e[0] for e in entry_ref) + (
            s, 0, jnp.maximum(i - (steps - 1 - pos_ref[s] // block), 0))

    prefetch = (pos.astype(jnp.int32),) if entry is None else (
        pos.astype(jnp.int32), jnp.asarray(entry, jnp.int32).reshape(1))

    out = pl.pallas_call(
        functools.partial(_kernel, block=block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(S, steps),
            in_specs=[
                pl.BlockSpec((1, G, Rp, C), lambda s, i, *p: (s, 0, 0, 0)),
                pl.BlockSpec(lead + (1, G * C, block), rows_at),
                pl.BlockSpec(lead + (1, G * C, block), rows_at),
            ],
            out_specs=pl.BlockSpec((1, G, Rp, C),
                                   lambda s, i, *p: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, Rp, 1), jnp.float32),
                pltpu.VMEM((G, Rp, 1), jnp.float32),
                pltpu.VMEM((G, Rp, C), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, G, Rp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_attention._interpret(),
        name="ragged_attention",
    )(*prefetch, q, k_rows, v_rows)
    return out[:, :, :R]


def paired_queries(q, pairs: int, head_dim: int):
    """The differential-attention family's packing: ``q (S, heads d)``
    with heads paired in order and ``g`` query pairs a K/V pair becomes
    ``(S, pairs, 2 g, 2 d)``: row ``(g, j)`` of group ``n`` holds query
    head ``j`` of query pair ``g`` in columns ``j d .. (j + 1) d`` (K
    head ``2 n + j``'s channels) and zeros beside, so that one product
    with the pair's ``2 d`` K channels is each head against its own K
    head, and one with the pair's ``2 d``-wide V is every head's read
    of it."""
    S = q.shape[0]
    q = q.reshape(S, pairs, -1, 2, 1, head_dim)
    own = jnp.eye(2, dtype=q.dtype)[:, :, None]
    return (q * own).reshape(S, pairs, -1, 2 * head_dim)


def paired_decode_attention(q, k_rows, v_rows, pos, head_dim: int):
    """What dense differential attention's second product gives, over
    each slot's live rows: ``q (S, heads d)``, rows ``(S, kv, L)`` →
    ``(S, pairs, g, 2, 2 d)`` float32, the two softmax maps' reads of
    their pair's V."""
    S = q.shape[0]
    pairs = k_rows.shape[1] // (2 * head_dim)
    out = ragged_attention(paired_queries(q, pairs, head_dim), k_rows,
                           v_rows, pos, 1.0 / math.sqrt(head_dim))
    return out.reshape(S, pairs, -1, 2, 2 * head_dim)
