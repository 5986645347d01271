"""Mixture-of-experts with expert parallelism over the ``ep`` mesh axis.

NEW capability beyond the reference (SURVEY.md 2.3 lists EP/MoE as
ABSENT).  Design (tpu-first): experts are ONE set of stacked parameters
(leading dim = num_experts) so a PartitionSpec ``P('ep', ...)`` shards
them; token dispatch/combine are dense einsums against a capacity-bucketed
one-hot mask (Shazeer/GShard style), which GSPMD turns into all-to-all
over ICI when the expert dim is sharded — no manual collective calls.

Beside it, the DROPLESS top-k expert layer as pure functions
(:func:`route`, then :func:`grouped_experts` or :func:`dense_experts`): every token's ``top_k`` choices
among all the experts are honoured, and the caller says which range of
the experts it HOLDS (one chip's share of an expert-parallel
deployment).  The held experts' part of the result is computed for the
tokens routed to them and nothing is added for an absent expert.  The
zoo's ``cohere2moe`` family and ``serving.moe`` both call these;
``MoEDense`` above keeps its capacity mask until it is retired onto them
(ROADMAP).
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Optional

import jax

from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import ops as ndops
from ..ndarray.ndarray import NDArray
from .spmd import DEFAULT_TRANSFORMER_RULES, PartitionRules

__all__ = ["MoEDense", "MOE_RULES", "MOE_TRANSFORMER_RULES",
           "collect_aux_losses", "route", "dense_experts",
           "grouped_experts"]


# Active aux-loss collector (trace-safe channel from MoE layers to the
# trainer's objective; ``self.aux_loss`` would leak tracers under jit).
_collector: Optional[list] = None


@contextlib.contextmanager
def collect_aux_losses():
    """Collect MoE load-balancing losses raised during ``forward``.

    SPMDTrainer wraps its traced loss computation in this context and adds
    the collected terms to the objective inside the same trace. Yields the
    list that forward() appends NDArray aux-loss terms to."""
    global _collector
    prev = _collector
    _collector = []
    try:
        yield _collector
    finally:
        _collector = prev


# Shard stacked expert weights over ep; everything else replicated.
MOE_RULES = PartitionRules([
    (r"expert_w1$", P("ep", None, None)),
    (r"expert_b1$", P("ep", None)),
    (r"expert_w2$", P("ep", None, None)),
    (r"expert_b2$", P("ep", None)),
])

# MoE transformer on a combined mesh (e.g. {"dp": 2, "ep": 4}): expert
# weights over ep, attention/FFN/embedding over tp when present, batch
# over dp via the trainer's data spec.
MOE_TRANSFORMER_RULES = MOE_RULES + DEFAULT_TRANSFORMER_RULES


class MoEDense(HybridBlock):
    """Routed mixture of expert FFNs (GShard-style, top-1 or top-2).

    Input (B, T, d) or (N, d); each token goes to its argmax expert
    (``top_k=2`` adds the runner-up with renormalized combine weights
    and a queue appended after all first choices),
    bucketed to ``capacity_factor * N / num_experts`` slots per expert.
    Overflow tokens produce ZERO output — wrap the layer in an external
    residual connection (as Switch Transformer does) so they pass through.
    The load-balancing auxiliary loss (fraction·probability dot product,
    Switch-Transformer eq. 4) is stored on ``self.aux_loss`` after eager
    forwards; under a traced step (SPMDTrainer) it is instead delivered
    through ``collect_aux_losses`` and added to the objective.
    """

    def __init__(self, num_experts: int, hidden_size: int,
                 units: Optional[int] = None, activation: str = "gelu",
                 capacity_factor: float = 1.25, dtype: Any = "float32",
                 top_k: int = 1, router_z_loss: float = 0.0,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        if num_experts < 1:
            raise MXNetError("num_experts must be >= 1")
        if top_k not in (1, 2):
            raise MXNetError("top_k must be 1 or 2")
        if top_k > num_experts:
            raise MXNetError(
                f"top_k={top_k} needs at least that many experts "
                f"(got num_experts={num_experts})")
        self._top_k = top_k
        self._z_coef = float(router_z_loss)
        self._E = num_experts
        self._H = hidden_size
        self._units = units          # defaults to input dim (residual FFN)
        self._act = activation
        self._cf = capacity_factor
        self.gate = Parameter("gate", shape=(num_experts, 0), dtype=dtype)
        self.expert_w1 = Parameter("expert_w1",
                                   shape=(num_experts, 0, hidden_size),
                                   dtype=dtype)
        self.expert_b1 = Parameter("expert_b1",
                                   shape=(num_experts, hidden_size),
                                   dtype=dtype, init="zeros")
        self.expert_w2 = Parameter("expert_w2",
                                   shape=(num_experts, hidden_size, 0),
                                   dtype=dtype)
        self.expert_b2 = Parameter("expert_b2", shape=(num_experts, 0),
                                   dtype=dtype, init="zeros")
        self.aux_loss: Optional[NDArray] = None

    def _finish_init(self, d: int) -> None:
        units = self._units or d
        if not self.gate.is_initialized:
            self.gate._finish_deferred_init((self._E, d))
            self.expert_w1._finish_deferred_init((self._E, d, self._H))
            self.expert_b1._finish_deferred_init((self._E, self._H))
            self.expert_w2._finish_deferred_init((self._E, self._H, units))
            self.expert_b2._finish_deferred_init((self._E, units))

    def forward(self, x: NDArray) -> NDArray:
        shape = x.shape
        d = shape[-1]
        self._finish_init(d)
        flat = x.reshape((-1, d))                       # (N, d)
        N = flat.shape[0]
        E = self._E
        C = max(1, int(math.ceil(self._cf * N / E)))

        logits = ndops.dot(flat, self.gate.data().T)    # (N, E)
        from ..ops import nn as npx
        probs = npx.softmax(logits, axis=-1)
        top_e = ndops.argmax(logits, axis=-1)           # (N,)
        e_hot = ndops.one_hot(top_e, E, dtype=x.dtype)  # (N, E)
        p1 = (probs * e_hot).sum(axis=-1)               # (N,)

        # first-choice capacity queues (position of each token within its
        # expert's queue; tokens past capacity produce zero output — the
        # external residual carries them, Switch-Transformer style)
        pos1 = ndops.cumsum(e_hot, axis=0) * e_hot - e_hot   # (N, E)
        keep1 = (pos1 < float(C)).astype(x.dtype) * e_hot
        pos_idx1 = (pos1 * keep1).sum(axis=-1)               # (N,)
        c_hot1 = ndops.one_hot(pos_idx1, C, dtype=x.dtype)   # (N, C)
        d1 = ndops.einsum("ne,nc->nec", keep1, c_hot1)       # (N, E, C)

        if self._top_k == 2:
            # second choice: argmax with the first expert masked out;
            # its queue appends AFTER every first-choice token (GShard
            # top-2 priority), combine weights renormalized over the pair
            probs2 = probs * (1.0 - e_hot)
            e2_hot = ndops.one_hot(ndops.argmax(probs2, axis=-1), E,
                                   dtype=x.dtype)            # (N, E)
            p2 = (probs2 * e2_hot).sum(axis=-1)
            cnt1 = e_hot.sum(axis=0)                         # (E,)
            pos2 = (ndops.cumsum(e2_hot, axis=0) * e2_hot - e2_hot
                    + e2_hot * cnt1.reshape((1, E)))
            keep2 = (pos2 < float(C)).astype(x.dtype) * e2_hot
            pos_idx2 = (pos2 * keep2).sum(axis=-1)
            c_hot2 = ndops.one_hot(pos_idx2, C, dtype=x.dtype)
            d2 = ndops.einsum("ne,nc->nec", keep2, c_hot2)
            denom = p1 + p2 + 1e-9
            w1, w2 = p1 / denom, p2 / denom
            dispatch = d1 + d2
            combine = d1 * w1.reshape((N, 1, 1)) \
                + d2 * w2.reshape((N, 1, 1))
        else:
            dispatch = d1
            combine = d1 * p1.reshape((N, 1, 1))

        # aux load-balance loss: E * sum_e fraction_e * mean-prob_e
        # (first-choice fractions, Switch-Transformer eq. 4), plus the
        # router z-loss mean(logsumexp(logits)^2) that keeps gate logits
        # from drifting large (ST-MoE)
        frac = e_hot.mean(axis=0)                            # (E,)
        mean_p = probs.mean(axis=0)
        aux = (frac * mean_p).sum() * float(E)
        if self._z_coef:
            zmax = logits.max(axis=-1, keepdims=True)
            z = ((logits - zmax).exp().sum(axis=-1)).log() \
                + zmax.squeeze(-1)
            aux = aux + float(self._z_coef) * (z * z).mean()
        if _collector is not None:
            _collector.append(aux)
        if not isinstance(aux._data, jax.core.Tracer):
            self.aux_loss = aux

        # dispatch -> expert FFN (stacked weights) -> combine
        xe = ndops.einsum("nec,nd->ecd", dispatch, flat)     # (E, C, d)
        h = ndops.einsum("ecd,edh->ech", xe, self.expert_w1.data())
        h = h + self.expert_b1.data().reshape((E, 1, self._H))
        h = npx.gelu(h) if self._act == "gelu" else npx.relu(h)
        ye = ndops.einsum("ech,ehu->ecu", h, self.expert_w2.data())
        ye = ye + self.expert_b2.data().reshape((E, 1, -1))
        out = ndops.einsum("nec,ecu->nu", combine, ye)       # (N, units)

        units = out.shape[-1]
        return out.reshape(tuple(shape[:-1]) + (units,))


# ---------------------------------------------------------------------------
# the dropless layer: pure functions over (T, d) tokens
# ---------------------------------------------------------------------------

@jax.named_scope("experts/route")
def route(h, router_w, top_k: int, held, valid=None):
    """Sigmoid top-k routing over ALL the experts, for a caller that
    holds the experts ``held = (lo, hi)`` of them.

    ``h (T, d)``, ``router_w (E, d)``.  Scores ``sigmoid(h Wr)`` are
    float32; a token's weights are its ``top_k`` scores over their sum.
    Returns ``(local, weights, load, scores)``: ``local (T, k)`` int32 is
    the choice's index among the held experts, or ``hi - lo`` where the
    chosen expert is absent (or the token is not ``valid``: a prompt's
    padding); ``weights (T, k)`` float32; ``load (hi - lo,)`` int32 the
    choices that fell on each held expert; ``scores (T, E)``."""
    import jax.numpy as jnp
    from jax import lax
    lo, hi = held
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", h, router_w, preferred_element_type=jnp.float32))
    top, ids = lax.top_k(scores, top_k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    here = (ids >= lo) & (ids < hi)
    if valid is not None:
        here &= valid[:, None]
    local = jnp.where(here, ids - lo, hi - lo).astype(jnp.int32)
    load = jnp.sum(local[..., None] == jnp.arange(hi - lo), axis=(0, 1),
                   dtype=jnp.int32)
    return local, weights, load, scores


def _swiglu(gate_up, dtype):
    import jax.numpy as jnp
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up).astype(dtype)


@jax.named_scope("experts/routed")
def dense_experts(h, local, weights, w_in, w_out):
    """The held experts' part of the output as ONE batched product over
    all of them, every token through every held expert and weighted 0
    where it was not routed: ``n`` times the needed FLOPs, but each
    expert's matrices are read once, which is all a decode step's few
    tokens cost.  ``w_in (n, d, 2 f)`` (gate then up), ``w_out (n, f,
    d)``; returns ``(T, d)`` float32."""
    import jax.numpy as jnp
    n = w_in.shape[0]
    combine = jnp.sum((local[..., None] == jnp.arange(n))
                      * weights[..., None], axis=1)             # (T, n)
    x = jnp.broadcast_to(h, (n,) + h.shape)
    act = _swiglu(jnp.einsum("etd,edf->etf", x, w_in,
                             preferred_element_type=jnp.float32), h.dtype)
    y = jnp.einsum("etf,efd->etd", act, w_out,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, combine)


# megablox's tile: rows of (token, choice) pairs, contraction, output
# columns.  From chip measurements, PERF.md section 6 (PR 32).
GMM_TILING = (128, 1024, 1024)


def _gmm(rows, w, sizes):
    """``rows[segment e] @ w[e]`` for the segments ``sizes`` names, in
    float32: the ``megablox`` grouped matmul that ships inside jax, a
    Pallas kernel whose grid visits the row tiles the segments touch
    and no other (interpret mode on the CPU, as every kernel here)."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import megablox
    from ..ops.pallas.attention import _interpret
    tm, tk, tn = GMM_TILING
    return megablox.gmm(
        rows, w, sizes, jnp.float32,
        (min(tm, rows.shape[0]), min(tk, w.shape[1]), min(tn, w.shape[2])),
        interpret=_interpret())


@jax.named_scope("experts/routed")
def grouped_experts(h, local, weights, load, w_in, w_out):
    """The same part by segments: the (token, choice) pairs sorted by
    held expert, the absent ones last, and one grouped product over the
    segments ``load`` names.  It reads each hit expert's matrices once
    and computes the routed rows alone, where the batched form's FLOPs
    grow 16-fold with a prompt's tokens; the sort, the gather and the
    way back cost a decode step more than they save it (PERF.md section
    6, PR 32: this, the batched form and ``jax.lax.ragged_dot``, which
    on a v5e takes 3.8 ms for ``w_in`` whatever the rows).  Returns
    ``(T, d)`` float32."""
    import jax.numpy as jnp
    T, k = local.shape
    n = w_in.shape[0]
    pairs = T * k
    # whole row tiles for the kernel
    pad = -pairs % min(GMM_TILING[0], -(-pairs // 8) * 8)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    # rows past the segments belong to no expert and the kernel leaves
    # them unwritten, forward and backward: selected away on the way in
    # and after each product, so that nothing of them reaches the
    # result or a gradient
    live = jnp.pad(flat[order] < n, (0, pad))[:, None]
    rows = jnp.where(live, jnp.pad(h[order // k], ((0, pad), (0, 0))), 0)
    act = _swiglu(jnp.where(live, _gmm(rows, w_in, load), 0.0), h.dtype)
    y = jnp.where(live, _gmm(act, w_out, load), 0.0)[:pairs] \
        * weights.reshape(-1)[order][:, None]
    back = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    return jnp.sum(y[back].reshape(T, k, -1), axis=1)
