"""Delta step — one token a slot through a Gated DeltaNet layer's state,
in place.

A decode step of the delta-rule family (``gluon.model_zoo.olmo_hybrid``)
moves every slot's matrix state of every head one token on::

    S = alpha S;  S = S + k (beta (v - S^T k))^T;  o = S^T q

``S`` is float32, ``dk x dv`` a head (96 x 192 published: 2.2 MB a slot
and layer), and the step does ~8 operations an element of it: it is
bound by reading and writing the state, and in XLA's hands the decay,
``S^T k``, the rank-one write and ``S^T q`` can each stream it.  This
kernel reads every slot's state once and writes it once, into the same
buffer (``input_output_aliases``).

Layout: the cache holds a slot's state as ``(dk, heads * dv)``, head
``h`` in lanes ``h dv .. (h + 1) dv`` (``to_lanes``).  With ``dv`` 192
a head's ``(dk, dv)`` alone would be padded to 256 lanes in HBM; heads
side by side along the lanes are whole 128-lane tiles and nothing is
padded.  A grid step takes ``block_heads`` heads of one slot (a block
``(dk, block_heads dv)``) and walks them a *unit* at a time: the fewest
heads whose lanes are whole tiles (2 at ``dv`` 192).  Inside a unit a
lane's head is ``lane // dv``; that head's ``k`` (and ``q``) down the
sublanes is selected into every lane it owns, so each product is an
elementwise multiply and a reduction down the sublanes, on the vector
unit, with no matrix unit and no relayout.

``q`` and ``k`` come with ``dk`` on sublanes and the block's heads on
lanes (``(S, blocks, dk, block_heads)``), ``v``, ``alpha`` and ``beta``
lane-dense in the state's lane order (``alpha`` and ``beta`` repeated
over their head's ``dv`` lanes by the caller: 23 KB a slot).  On the
CPU the kernel runs in interpret mode (``attention._interpret``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

LANES = 128
# a grid step's block of state at most: 10 heads of 96 x 192 float32
# are 737 KB, four such buffers in flight (in and out, double-buffered)
# ~3 MB of VMEM
BLOCK_BYTES = 1 << 20


def unit_heads(dv: int) -> int:
    """The fewest heads whose ``dv`` lanes each make whole 128-lane
    tiles together."""
    return LANES // math.gcd(int(dv), LANES)


def block_heads(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step takes: the most whole units that divide
    ``heads`` with a block of at most ``BLOCK_BYTES``; all of them where
    no unit divides them (a size only the CPU tests use)."""
    unit = unit_heads(dv)
    fits = [n for n in range(unit, heads + 1, unit)
            if heads % n == 0 and 4 * dk * dv * n <= BLOCK_BYTES]
    return max(fits) if fits else heads


def to_lanes(s):
    """``(..., heads, dk, dv)`` -> the cache's ``(..., dk, heads dv)``."""
    *lead, h, dk, dv = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, dk, h * dv)


def from_lanes(s, heads: int):
    """The inverse of :func:`to_lanes`."""
    *lead, dk, hv = s.shape
    return jnp.moveaxis(s.reshape(*lead, dk, heads, hv // heads), -2, -3)


def _kernel(q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, o_ref, s_out, *,
            dv, unit):
    nb = q_ref.shape[-1]
    width = min(unit, nb) * dv
    head = lax.broadcasted_iota(jnp.int32, (1, width), 1) // dv
    q, k = q_ref[0, 0], k_ref[0, 0]             # (dk, heads of the block)
    dk = q.shape[0]

    def down(x, first):
        # (dk, width): lane j holds column ``first + head(j)`` of x
        out = jnp.broadcast_to(x[:, first:first + 1], (dk, width))
        for j in range(1, width // dv):
            out = jnp.where(head == j, jnp.broadcast_to(
                x[:, first + j:first + j + 1], (dk, width)), out)
        return out

    for u in range(nb * dv // width):
        lanes = pl.ds(u * width, width)
        first = u * (width // dv)
        kk = down(k, first)
        s = a_ref[0, :, lanes] * s_ref[0, :, lanes]
        ks = jnp.sum(s * kk, axis=0, keepdims=True)
        s = s + kk * (b_ref[0, :, lanes] * (v_ref[0, :, lanes] - ks))
        s_out[0, :, lanes] = s
        o_ref[0, :, lanes] = jnp.sum(s * down(q, first), axis=0,
                                     keepdims=True)


def delta_step(state, q, k, v, alpha, beta):
    """One token a slot: ``state (S, dk, H dv)`` float32 (the cache's
    lanes, :func:`to_lanes`), ``q``, ``k (S, H, dk)``, ``v (S, H, dv)``,
    ``alpha``, ``beta (S, H)``, all float32.  Returns ``o (S, H, dv)``
    and the new state, which IS ``state``'s buffer (aliased: donate it
    where the call is jitted)."""
    S, dk, hv = state.shape
    H = q.shape[1]
    dv = hv // H
    nb = block_heads(H, dk, dv)
    G = H // nb
    f32 = jnp.float32

    def heads_on_lanes(x):
        return jnp.swapaxes(x.astype(f32).reshape(S, G, nb, dk), 2, 3)

    def lane_dense(x, per_head):
        x = x.astype(f32)
        if per_head:
            x = jnp.repeat(x, dv, axis=-1)
        return x.reshape(S, 1, hv)

    heads_spec = pl.BlockSpec((1, 1, dk, nb), lambda s, g: (s, g, 0, 0))
    lane_spec = pl.BlockSpec((1, 1, nb * dv), lambda s, g: (s, 0, g))
    state_spec = pl.BlockSpec((1, dk, nb * dv), lambda s, g: (s, 0, g))
    o, state = pl.pallas_call(
        functools.partial(_kernel, dv=dv, unit=unit_heads(dv)),
        grid=(S, G),
        in_specs=[heads_spec, heads_spec, lane_spec, lane_spec, lane_spec,
                  state_spec],
        out_specs=[lane_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((S, 1, hv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_attention._interpret(),
        name="delta_step",
    )(heads_on_lanes(q), heads_on_lanes(k), lane_dense(v, False),
      lane_dense(alpha, True), lane_dense(beta, True), state)
    return o.reshape(S, H, dv), state
