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

A looped family's pass WRITES the token's column into the rows it then
reads, entry after entry of a stacked cache, 192 times a step on calls
a twentieth the size: :func:`append_and_attend` is that pass in one
call, the same block step (``_attend``) under a walk of its own: the
rows stay in HBM and the kernel copies a slot's ``pos // B + 1`` live
blocks itself (no idle steps, so a smaller ``B``: ``append_block``),
selects the new column into the last of them in VMEM and copies the
tile that took it back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention
from .column_write import LANES, _WORD, _words

# positions a block.  Fixed from chip measurements (PERF.md section 6,
# PR 31); a bucket shorter than this is read as one block.
ROW_BLOCK = 512

_SUBLANES = 8


def row_block(length: int) -> int:
    """Positions a block of ``ragged_attention``'s grid over rows
    ``length`` long."""
    return min(ROW_BLOCK, int(length))


def append_block(length: int) -> int:
    """Positions a block of ``append_and_attend``'s copy loop over rows
    ``length`` long: an eighth of them, from one tile column to
    ``ROW_BLOCK``.  The loop has no idle steps to pay for, so its block
    follows the rows: a slot's are a third of the bucket on average and
    the last block fetched is half dead, which a larger block pays in
    bytes and a smaller one in fixed cost a block (~0.6 us; PERF.md
    section 6, PR 38: 128 and 256 tie at 1024, 512 loses 20 %)."""
    length = int(length)
    return min(length, max(LANES, min(ROW_BLOCK, length // 8)))


def blocks_read(pos, length: int, block=None):
    """``(read, all)``: the position blocks of ``block`` positions
    (``row_block(length)`` unless said) a call at per-slot positions
    ``pos`` (a host vector) fetches, and those a dense read of every
    slot's ``length`` rows would."""
    block = block or row_block(length)
    return (int((pos // block + 1).sum()),
            int(pos.shape[0]) * (int(length) // block))


def _attend(q_ref, rows, seen, scale, m_sc, l_sc, acc_sc):
    """One position block into every group's online softmax:
    ``rows(n)`` is group ``n``'s ``(C, block)`` K and V of it, ``seen
    (1, block)`` the columns that count (None: all of them)."""
    for n in range(acc_sc.shape[0]):
        kn, vn = rows(n)
        prec = _attention._prec(kn.dtype)
        sc = jax.lax.dot_general(
            q_ref[0, n], kn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale
        if seen is not None:
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


def _set(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, _attention._NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _seen(j, block, pos):
    """``(1, block)``: the columns of block ``j`` up to ``pos``."""
    return j * block + jax.lax.broadcasted_iota(
        jnp.int32, (1, block), 1) <= pos


def _kernel(*refs, block, scale):
    # refs: pos, the entry where the rows are stacked, then q, k, v, o
    # and the scratch
    pos_ref = refs[0]
    q_ref, k_ref, v_ref, o_ref, *stats = refs[-7:]
    s, i = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[s]
    final = pl.num_programs(1) - 1
    # the block this step walks; negative on the slot's idle steps
    j = i - (final - pos // block)
    C = stats[2].shape[2]

    def rows(n):
        return (k_ref[0, n * C:(n + 1) * C, :],
                v_ref[0, n * C:(n + 1) * C, :])

    @pl.when(i == 0)
    def _init():
        _set(*stats)

    @pl.when(jnp.logical_and(j >= 0, i < final))
    def _interior():
        _attend(q_ref, rows, None, scale, *stats)

    @pl.when(i == final)
    def _last():
        _attend(q_ref, rows, _seen(j, block, pos), scale, *stats)
        o_ref[0] = stats[2][...] / stats[1][...]


def _append_kernel(pos_ref, entry_ref, q_ref, kc_ref, vc_ref, k_hbm, v_hbm,
                   o_ref, ko_hbm, vo_hbm, kbuf, vbuf, sem, out_sem, count,
                   *stats, block, tile, scale):
    s, S = pl.program_id(0), pl.num_programs(0)
    e = entry_ref[0]
    pos = pos_ref[s]
    last = pos // block
    C = stats[2].shape[2]
    sides = ((k_hbm, kbuf, kc_ref, ko_hbm), (v_hbm, vbuf, vc_ref, vo_hbm))

    def fetch(slot, j, b):
        """Block ``j`` of ``slot``'s rows into buffer ``b``, K and V."""
        return [pltpu.make_async_copy(
            hbm.at[e, slot, :, pl.ds(pl.multiple_of(j * block, block),
                                     block)],
            buf.at[b], sem.at[b, i])
            for i, (hbm, buf, _, _) in enumerate(sides)]

    def tile_out(b, t):
        """Tile column ``t`` of buffer ``b``, the one that holds ``pos``,
        back to the cache."""
        at = pl.multiple_of((pos // tile) * tile, tile)
        return [pltpu.make_async_copy(
            buf.at[b, :, t * tile:(t + 1) * tile],
            out.at[e, s, :, pl.ds(at, tile)], out_sem.at[i])
            for i, (_, buf, _, out) in enumerate(sides)]

    @pl.when(s == 0)
    def _first():
        count[0] = 0
        for c in fetch(0, 0, 0):
            c.start()

    _set(*stats)
    # the buffers alternate over the call's blocks, not the slot's
    base = count[0]

    def arrive(j):
        """Waits for block ``j`` of this slot, with the one after it
        (the next slot's first behind this slot's last) on its way into
        the other buffer; the buffer it is in."""
        b = (base + j) % 2

        @pl.when(j < last)
        def _next_block():
            for c in fetch(s, j + 1, 1 - b):
                c.start()

        @pl.when(jnp.logical_and(j == last, s + 1 < S))
        def _next_slot():
            for c in fetch(s + 1, 0, 1 - b):
                c.start()

        for c in fetch(s, j, b):
            c.wait()
        return b

    def rows_in(b):
        return lambda n: (kbuf[b, n * C:(n + 1) * C, :],
                          vbuf[b, n * C:(n + 1) * C, :])

    def interior(j, carry):
        _attend(q_ref, rows_in(arrive(j)), None, scale, *stats)
        return carry

    jax.lax.fori_loop(0, last, interior, 0)
    b = arrive(last)

    # column_write's move, on the tile column of the block that holds
    # pos: the slot's words, one a lane, down the tile's sublanes in
    # every lane, and the select keeps lane pos % tile of them.  In the
    # buffer itself: the block is attended as the cache will hold it,
    # and the tile goes back from there while it is
    for t in range(block // tile):
        @pl.when(pos % block // tile == t)
        def _write():
            for _, buf, col_ref, _ in sides:
                words = pltpu.bitcast(buf[b, :, t * tile:(t + 1) * tile],
                                      _WORD)
                col = jnp.broadcast_to(col_ref[0], words.shape[::-1]).T
                hit = jax.lax.broadcasted_iota(
                    jnp.int32, words.shape, 1) == pos % tile
                buf[b, :, t * tile:(t + 1) * tile] = pltpu.bitcast(
                    jnp.where(hit, col, words), buf.dtype)
            for c in tile_out(b, t):
                c.start()
    _attend(q_ref, rows_in(b), _seen(last, block, pos), scale, *stats)
    o_ref[0] = stats[2][...] / stats[1][...]
    count[0] = base + last + 1
    # before the next slot's second block may land in this buffer
    for c in tile_out(b, 0):
        c.wait()


def _padded_queries(q):
    """``q (S, G, R, C)`` with ``R`` padded to whole sublanes."""
    pad = -q.shape[2] % _SUBLANES
    return jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q


def _stats(G, Rp, C):
    return [pltpu.VMEM((G, Rp, 1), jnp.float32),
            pltpu.VMEM((G, Rp, 1), jnp.float32),
            pltpu.VMEM((G, Rp, C), jnp.float32)]


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
    q = _padded_queries(q)
    Rp = q.shape[2]
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
            scratch_shapes=_stats(G, Rp, C)),
        out_shape=jax.ShapeDtypeStruct((S, G, Rp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_attention._interpret(),
        name="ragged_attention",
    )(*prefetch, q, k_rows, v_rows)
    return out[:, :, :R]


def append_and_attend(q, k_rows, v_rows, k_new, v_new, pos, scale: float,
                      entry):
    """A pass of a looped family in ONE call: every slot's new K and V
    column written into entry ``entry`` of the STACKED rows
    ``(E, S, G C, L)`` at ``pos[s]``, and ``ragged_attention`` over that
    entry as written.  ``q (S, G, R, C)``; ``k_new`` / ``v_new
    (S, G C)`` in the rows' dtype, lane-dense as the projection leaves
    them; ``pos (S,)`` int32, clamped into ``0 .. L - 1`` as
    ``write_columns`` clamps it (a free slot rides at 0); ``entry`` an
    int32 scalar, traced.  Returns ``(attention (S, G, R, C) float32,
    k_rows, v_rows)``, the stacks aliased to their operands.

    The rows stay in HBM (no block spec) and the kernel, a grid over
    slots, copies a slot's live blocks ``0 .. pos[s] // B`` itself
    (``B = append_block(L)``), two buffers a side, the next block (the
    next slot's first behind a slot's last) on its way while one is
    attended: ``pos // B + 1`` copies a slot and no idle step.  On a
    slot's last block, which holds ``pos[s]``, the new column is
    selected into its lane IN the buffer (``column_write``'s move:
    32-bit words, no arithmetic), so the block is attended bit for bit
    as the cache will hold it and the result is what write-then-read
    gives; the 128-position tile column of the buffer that holds the
    lane is then copied back to the stack, the only bytes the call
    writes there, fetched once.  Every other element of both stacks is
    never touched."""
    S, G, R, C = q.shape
    L = k_rows.shape[-1]
    if k_rows.ndim != 4:
        raise ValueError(f"rows {k_rows.shape}: stacked (E, S, channels, "
                         "L) with the entry to write and read")
    for rows, new in ((k_rows, k_new), (v_rows, v_new)):
        if rows.shape[1:] != (S, G * C, L) or new.shape != (S, G * C) \
                or new.dtype != rows.dtype:
            raise ValueError(f"column {new.shape} {new.dtype} does not "
                             f"fit rows {rows.shape} {rows.dtype} of "
                             f"{S} slots and {G * C} channels")
    block = append_block(L)
    tile = min(LANES, block)
    if L % block:
        raise ValueError(f"rows of {L} positions are not whole blocks "
                         f"of {block}")
    q = _padded_queries(q)
    Rp = q.shape[2]
    words = [_words(new) for new in (k_new, v_new)]
    pos = jnp.clip(pos.astype(jnp.int32), 0, L - 1)
    here = lambda s, *p: (s, 0, 0, 0)                    # noqa: E731
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out, k_rows, v_rows = pl.pallas_call(
        functools.partial(_append_kernel, block=block, tile=tile,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, G, Rp, C), here)] + [
                pl.BlockSpec((1, 1, w.shape[2]), lambda s, *p: (s, 0, 0))
                for w in words] + [anywhere] * 2,
            out_specs=[pl.BlockSpec((1, G, Rp, C), here)] + [anywhere] * 2,
            scratch_shapes=[
                pltpu.VMEM((2, G * C, block), k_rows.dtype),
                pltpu.VMEM((2, G * C, block), v_rows.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)] + _stats(G, Rp, C)),
        out_shape=[jax.ShapeDtypeStruct((S, G, Rp, C), jnp.float32),
                   jax.ShapeDtypeStruct(k_rows.shape, k_rows.dtype),
                   jax.ShapeDtypeStruct(v_rows.shape, v_rows.dtype)],
        # behind pos and entry: q, the two columns, then K and V
        input_output_aliases={5: 1, 6: 2},
        # the buffers alternate from slot to slot: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_attention._interpret(),
        name="ragged_attention",
    )(pos, jnp.asarray(entry, jnp.int32).reshape(1), q, *words,
      k_rows, v_rows)
    return out[:, :, :R], k_rows, v_rows


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
