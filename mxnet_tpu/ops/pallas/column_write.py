"""Column write — the new token's K/V column of every slot, in place.

A decode step appends, for every slot ``s``, one column to that slot's
rows in the cache (``serving.kv_cache``: ``(S, channels, L)``, positions
last): the token's K (and V) channels at position ``at[s]``, or at
``at[s] % window`` of a ring.  A TPU scatter wants its update window on
the minor axes and relays the whole buffer around it (PERF.md, PR 27);
one ``dynamic_update_slice`` a slot is in place but pays ~3.5 us an
operation whatever it moves (PR 33).  This kernel is ONE call a layer:
a grid over slots with ``at (S,)`` scalar-prefetched, the buffers
aliased to the results.

Grid step ``s`` fetches the one 128-position tile column of slot ``s``
that holds position ``at[s]`` (block ``(1, C, 128)`` at block index
``at[s] // 128``), selects the new column into lane ``at[s] % 128`` and
lets the pipeline write the tile back; a block the grid does not visit
is never touched, so every other element stays bit for bit what it was.
(Mosaic refuses a one-lane DMA into the tiled layout, "Slice shape
along dimension 2 must be aligned to tiling (128)", so a tile is the
least that can be written.)

The columns come lane-dense, as the projection leaves them: ``(S, C)``
with the channels on lanes, packed to 32-bit words (two bfloat16
channels a word, in the order a tile packs two sublane rows).  The
kernel turns slot ``s``'s row of words into the tile's column by a
32-bit transpose and views the tile as words while it selects, so the
write is a move in either dtype: no arithmetic, no conversion.  Handing
the columns over as ``(S, C, 1)`` would store each padded to 128 lanes,
as much again to write and to read as the tile itself.

Buffers that share ``at`` (a layer's K and V) go through one call.  On
the CPU the kernel runs in interpret mode (``attention._interpret``).

The hybrid and the sparse-expert families' decode steps call it.  The
looped family's pass makes the same move inside its one call
(``decode_attention.append_and_attend``: the tile that takes the column
is the read's last block, so it is fetched once); the stacked form here
(``entry=``) is what that call's written stacks are held to, bit for
bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

# positions a tile column: the lane width.  Rows shorter than it are
# one block.
LANES = 128

_WORD = jnp.uint32


def _kernel(at_ref, *refs, lanes, n):
    # behind ``at``: the entry where the buffers are stacked, then the
    # n columns, the n buffers and the n results
    refs = refs[-3 * n:]
    cols, bufs, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    lane = at_ref[pl.program_id(0)] % lanes
    for col_ref, buf_ref, out_ref in zip(cols, bufs, outs):
        tile = pltpu.bitcast(buf_ref[0], _WORD)
        # the slot's words, one a lane, down the tile's sublanes in
        # every lane; the select keeps lane at % lanes of them
        col = jnp.broadcast_to(col_ref[0], (lanes, tile.shape[0])).T
        hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == lane
        out_ref[0] = pltpu.bitcast(jnp.where(hit, col, tile),
                                   out_ref.dtype)


def _words(cols):
    """``cols (S, C, 1)`` or ``(S, C)`` as ``(S, 1, C / p)`` 32-bit
    words, ``p`` channels a word, channel ``p i`` in the low bits."""
    S, C = cols.shape[:2]
    p = _WORD.dtype.itemsize // cols.dtype.itemsize
    if p < 1 or C % p:
        raise ValueError(f"{C} channels of {cols.dtype} do not pack into "
                         "32-bit words")
    return jax.lax.bitcast_convert_type(
        cols.reshape(S, C // p, p), _WORD).reshape(S, 1, C // p)


@jax.jit
def write_columns(bufs, cols, at, entry=None):
    """Each ``buf (S, C, L)`` of ``bufs`` with column ``at[s]`` of slot
    ``s`` replaced by row ``s`` of its ``cols`` (``(S, C)``, or the
    ``(S, C, 1)`` a per-slot update takes) and every other element what
    it was; ``at (S,)`` int32, one for all the buffers, is clamped into
    ``0 .. L - 1`` as ``dynamic_update_slice`` clamps it.  The buffers
    must agree in ``S`` and ``L``.  Returns the tuple of written
    buffers, each aliased to its operand.  (Jitted so that a step's
    layers of one shape are traced once; the caller's program inlines
    it.)

    With ``entry`` (an int32 scalar, traced) the buffers are STACKED,
    ``(E, S, C, L)``, and the columns go into entry ``entry`` of each:
    it is scalar-prefetched beside ``at`` and picks the leading block
    index, so a caller inside a loop carries the whole stack through,
    aliased, and every other entry stays what it was."""
    bufs, cols = tuple(bufs), tuple(cols)
    lead = () if entry is None else (None,)
    if bufs[0].ndim != 3 + len(lead):
        raise ValueError(
            f"buffer {bufs[0].shape}: (S, C, L), or stacked "
            "(E, S, C, L) with the entry to write")
    S, _, L = bufs[0].shape[-3:]
    lanes = min(LANES, L)
    if L % lanes:
        raise ValueError(f"rows of {L} positions are not whole blocks "
                         f"of {lanes}")
    for buf, col in zip(bufs, cols):
        if buf.shape[-3::2] != (S, L) or buf.shape[:-3] != \
                bufs[0].shape[:-3] or col.shape[:2] != buf.shape[-3:-1] \
                or col.dtype != buf.dtype:
            raise ValueError(f"column {col.shape} {col.dtype} does not "
                             f"fit buffer {buf.shape} {buf.dtype}")
    at = jnp.clip(at.astype(jnp.int32), 0, L - 1)
    words = [_words(col) for col in cols]
    n = len(bufs)

    prefetch = (at,) if entry is None else (
        at, jnp.asarray(entry, jnp.int32).reshape(1))

    def tile_at(s, at_ref, *entry_ref):
        return tuple(e[0] for e in entry_ref) + (s, 0, at_ref[s] // lanes)

    tiles = [pl.BlockSpec(lead + (1, buf.shape[-2], lanes), tile_at)
             for buf in bufs]
    out = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(S,),
            in_specs=[pl.BlockSpec((1, 1, w.shape[2]),
                                   lambda s, *p: (s, 0, 0))
                      for w in words] + tiles,
            out_specs=tiles),
        out_shape=[jax.ShapeDtypeStruct(buf.shape, buf.dtype)
                   for buf in bufs],
        # the prefetched scalars, then the n columns, then the n buffers
        input_output_aliases={len(prefetch) + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_attention._interpret(),
        name="write_columns",
    )(*prefetch, *words, *bufs)
    return tuple(out)
