"""Pipeline parallelism — GPipe-style microbatch schedule over a mesh axis.

NEW capability beyond the reference (SURVEY.md 2.3): leezu/mxnet's closest
analog is manual ``ctx_group`` model parallelism with cross-device copy
nodes; it has no pipeline schedule.  Here stage parameters are stacked on a
leading axis sharded over ``pp``; microbatches flow stage-to-stage via
``ppermute`` inside a ``lax.scan`` (the scaling-book pipelining recipe),
so each hop is one ICI neighbor transfer and XLA overlaps compute with the
collective.

Schedule: ``num_microbatches + num_stages - 1`` ticks (the GPipe bubble);
differentiable end to end — reverse-mode runs the reverse schedule
automatically through the scan/ppermute transpose.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_train_grads", "GPTPipe",
           "PIPELINE_RULES"]


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: "jax.Array",
                   mesh: "jax.sharding.Mesh", axis: str = "pp",
                   num_microbatches: Optional[int] = None,
                   rng_key: Optional["jax.Array"] = None,
                   batch_axis: Optional[str] = None) -> "jax.Array":
    """Apply ``num_stages`` chained stages to ``x`` with a GPipe schedule.

    stage_fn(params_i, h) -> h' — one stage's computation; the activation
    shape must be the same for every stage (classic pipeline constraint).
    stage_params: pytree whose leaves have leading dim ``num_stages``
    (stage i's slice feeds stage i), sharded over mesh axis ``axis``.
    x: (B, ...) global batch; split into microbatches along dim 0.
    rng_key: when given, ``stage_fn`` is called as
    ``stage_fn(params_i, h, key)`` with a key folded per (tick, stage) —
    the plumbing that makes in-pipeline dropout draw fresh randomness for
    every microbatch at every stage (and regenerate identically in the
    scan's recompute-for-backward).
    batch_axis (r3): a mesh axis to shard each microbatch's batch dim
    over — pp COMPOSES with dp in one program (each dp row pipelines its
    own batch slice; gradient reduction over dp is GSPMD's psum as
    usual). Ignored when absent from the mesh or non-divisible.

    Returns stage_{N-1}(...stage_0(x)) with shape x.shape.
    """
    def call_stage(params, h, m, stage):
        # key folds on (microbatch, stage) — NOT the tick — so the 1F1B
        # backward's recompute (different tick) regenerates the same
        # dropout masks as the forward
        if rng_key is None:
            return stage_fn(params, h)
        key = jax.random.fold_in(jax.random.fold_in(rng_key, m), stage)
        return stage_fn(params, h, key)

    if axis not in mesh.axis_names:
        # degenerate: run stages sequentially on one device
        n = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        h = x
        for i in range(n):
            h = call_stage(jax.tree_util.tree_map(lambda a: a[i],
                                                  stage_params), h, i, i)
        return h

    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} must equal mesh "
                f"axis '{axis}' size {n_stages} (one stage per device)")
    n_micro = num_microbatches or n_stages
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible into {n_micro} "
                         f"microbatches")
    mb = B // n_micro
    x_mb = x.reshape((n_micro, mb) + x.shape[1:])

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params, x_mb):
        # params leaves: (1, ...) own stage slice; x_mb: (n_micro, mb, ...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        state0 = jnp.zeros_like(x_mb[0])
        out_buf0 = jnp.zeros_like(x_mb)

        @jax.checkpoint
        def tick(carry, t):
            state, out_buf = carry
            # stage 0 ingests microbatch t (clamped; masked by `where`)
            inp = x_mb[jnp.clip(t, 0, n_micro - 1)]
            feed = jnp.logical_and(stage == 0, t < n_micro)
            h = jnp.where(feed, inp, state)
            h = call_stage(params, h, jnp.clip(t - stage, 0, n_micro - 1),
                           stage)
            # last stage banks finished microbatch t-(n_stages-1)
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            out_buf = jnp.where(
                bank,
                jax.lax.dynamic_update_index_in_dim(out_buf, h, done_idx, 0),
                out_buf)
            # hand activations to the next stage
            state = jax.lax.ppermute(h, axis, perm)
            return (state, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (state0, out_buf0), jnp.arange(n_micro + n_stages - 1))
        return out_buf[None]

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    bax = (batch_axis if batch_axis and batch_axis in mesh.axis_names
           and batch_axis != axis and mb % mesh.shape[batch_axis] == 0
           else None)
    out = _shard_map(local, mesh,
                     in_specs=(pspec, P(None, bax)),
                     out_specs=P(axis, None, bax))(
        stage_params, x_mb)
    # the bank is only populated on the last stage; its slice is the result
    out = out[-1]
    return out.reshape((B,) + x.shape[1:])


# ---------------------------------------------------------------------------
# 1F1B schedule: hand-scheduled forward+backward in one pass
# ---------------------------------------------------------------------------

def _simulate_1f1b(S: int, M: int):
    """Host-side 1F1B schedule simulation → static per-tick work tables.

    Classic one-forward-one-backward discipline: stage ``s`` may hold at
    most ``S - s`` microbatches in flight (warmup), then strictly
    alternates backward/forward. Each global tick has a forward phase
    and a backward phase; activations/cotangents transfer at tick end
    and are consumable from the next tick (the last stage turns its own
    fresh forward around within the same tick).

    Returns int32 arrays ``(fwd, bwd, arr_f, arr_b)`` of shape (T, S):
    the microbatch each stage forwards / backwards at tick k (-1 idle),
    and the microbatch whose activation / cotangent ARRIVES at stage s
    at tick k (what the previous tick's ppermute carried).
    """
    import numpy as onp
    fwd_done = onp.full((S, M), -1, onp.int64)
    bwd_done = onp.full((S, M), -1, onp.int64)
    next_fwd = [0] * S
    next_bwd = [0] * S
    rows_f, rows_b = [], []
    k = 0
    while any(n < M for n in next_bwd):
        if k > 4 * (M + S) + 8:
            raise AssertionError("1F1B schedule simulation did not "
                                 f"converge (S={S}, M={M})")
        row_f = [-1] * S
        # forward phase: decisions depend only on prior ticks
        for s in range(S):
            m = next_fwd[s]
            if m >= M:
                continue
            if next_fwd[s] - next_bwd[s] >= S - s:   # 1F1B in-flight cap
                continue
            if s > 0 and not (0 <= fwd_done[s - 1][m] < k):
                continue
            row_f[s] = m
            fwd_done[s][m] = k
            next_fwd[s] += 1
        row_b = [-1] * S
        # backward phase: the last stage may consume its same-tick fwd
        for s in range(S):
            m = next_bwd[s]
            if m >= M:
                continue
            if s == S - 1:
                ok = 0 <= fwd_done[s][m] <= k
            else:
                ok = 0 <= bwd_done[s + 1][m] < k
            if ok:
                row_b[s] = m
                bwd_done[s][m] = k
                next_bwd[s] += 1
        rows_f.append(row_f)
        rows_b.append(row_b)
        k += 1
    fwd = onp.asarray(rows_f, onp.int32)
    bwd = onp.asarray(rows_b, onp.int32)
    T = fwd.shape[0]
    arr_f = onp.full((T, S), -1, onp.int32)
    arr_b = onp.full((T, S), -1, onp.int32)
    for kk in range(1, T):
        for s in range(1, S):
            arr_f[kk][s] = fwd[kk - 1][s - 1]
        for s in range(S - 1):
            arr_b[kk][s] = bwd[kk - 1][s + 1]
    # ring-safety: with S saved slots per stage, fwd of m must never
    # overwrite a residual whose backward is still pending
    for s in range(S):
        for m in range(S, M):
            assert bwd_done[s][m - S] < fwd_done[s][m], (s, m)
    return fwd, bwd, arr_f, arr_b


def pipeline_train_grads(stage_fn: Callable, loss_fn: Callable,
                         stage_params: Any, x: "jax.Array", y: "jax.Array",
                         mesh: "jax.sharding.Mesh", axis: str = "pp",
                         num_microbatches: Optional[int] = None,
                         rng_key: Optional["jax.Array"] = None,
                         head_params: Any = None):
    """One pipeline-parallel training pass with the 1F1B schedule:
    returns ``(mean_loss, stage_grads)`` in a single hand-scheduled
    sweep — no ``jax.grad`` over the whole pipeline.

    Versus the GPipe path (``jax.grad`` of :func:`pipeline_apply`):

    * **Memory**: GPipe holds all ``M`` microbatch residuals per stage
      until its reverse sweep; 1F1B holds at most ``S`` (the saved-input
      ring) — backward of microbatch m starts as soon as its forward
      leaves the last stage.
    * **Bubble**: both schedules idle (S-1)/(ticks) at the ramps; the
      tick count here is the simulated 1F1B length (~M + 2(S-1) double
      ticks vs GPipe's (M+S-1) forward + (M+S-1) reversed ticks).
    * Work units are wrapped in ``lax.cond`` so an idle stage SKIPS the
      compute (collectives stay outside the conditionals — every device
      reaches both ppermutes each tick).

    Backward recomputes each stage's forward from the saved input (the
    same remat tradeoff as the GPipe path's per-tick ``jax.checkpoint``).
    ``loss_fn(h_out, y_mb) -> scalar`` is evaluated at the last stage
    (masked elsewhere); grads come back stacked over ``axis`` like
    ``stage_params`` and are already divided by ``num_microbatches``.
    ``rng_key``: as in :func:`pipeline_apply`, folded per
    (microbatch, stage) so backward regenerates the forward's dropout.

    ``head_params`` (full-model 1F1B, r4): an optional pytree of
    last-stage head parameters (final norm, LM projection). When given,
    ``loss_fn(head_params, h_out, y_mb) -> scalar`` runs INSIDE the
    sweep at the last stage (guarded by ``lax.cond`` so interior stages
    skip the vocab matmul), and the return becomes ``(mean_loss,
    stage_grads, head_grads, dx)`` — ``head_grads`` matching
    ``head_params`` and ``dx`` the gradient w.r.t. ``x`` (stage 0's
    incoming cotangents, reassembled over microbatches), so the caller
    can chain embedding/backbone backward outside the pipeline. This is
    what lets a complete model (embed -> stages -> head) train under
    the 1F1B discipline rather than only the stage stack.

    Memory note: ``dx`` accumulates per microbatch, an input-batch-sized
    buffer — the same order as ``x`` itself, which every schedule holds
    for the whole sweep. The 1F1B O(S)-vs-O(M) advantage concerns the
    per-stage HIDDEN-activation residual ring, which stays S-slot here.
    """
    S = mesh.shape[axis]
    n_micro = num_microbatches or S
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible into {n_micro} "
                         f"microbatches")
    mbs = B // n_micro
    # 1F1B COMPOSES with dp (r5): the microbatch batch dim shards over
    # the mesh's dp axis — each dp row pipelines its own batch slice
    # through the same tick tables, and grads/loss psum over dp at the
    # end of the sweep (GSPMD's allreduce analog, but explicit because
    # the whole sweep lives inside one shard_map).
    dp_ax = ("dp" if ("dp" in mesh.axis_names and axis != "dp"
                      and mesh.shape["dp"] > 1) else None)
    dpn = mesh.shape[dp_ax] if dp_ax else 1
    if mbs % dpn:
        raise ValueError(f"microbatch size {mbs} not divisible by "
                         f"dp={dpn}")
    x_mb = x.reshape((n_micro, mbs) + x.shape[1:])
    y_mb = y.reshape((n_micro, mbs) + y.shape[1:])
    ftbl_np, btbl_np, af_np, ab_np = _simulate_1f1b(S, n_micro)
    T = ftbl_np.shape[0]
    perm_f = [(i, (i + 1) % S) for i in range(S)]
    perm_b = [((i + 1) % S, i) for i in range(S)]
    # shapes inside the shard_map are PER-DEVICE: dp splits the batch
    act_shape = (mbs // dpn,) + x.shape[1:]

    def _stage(params, h, m):
        if rng_key is None:
            return stage_fn(params, h)
        stage = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.fold_in(rng_key, m), stage)
        if dp_ax:
            # distinct dropout draws per dp row (rows hold different
            # examples — replicated masks would correlate them)
            key = jax.random.fold_in(key, jax.lax.axis_index(dp_ax))
        return stage_fn(params, h, key)

    def local(params, x_mb, y_mb, hparams=None):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        ftbl = jnp.asarray(ftbl_np)
        btbl = jnp.asarray(btbl_np)
        af = jnp.asarray(af_np)
        ab = jnp.asarray(ab_np)
        dt = x_mb.dtype
        zero_act = jnp.zeros(act_shape, dt)
        ring0 = jnp.zeros((S,) + act_shape, dt)

        def tick(carry, k):
            if head_params is None:
                (wire_f, wire_b, inbox_f, inbox_b, saved,
                 gacc, lacc) = carry
                hacc = dxacc = None
            else:
                (wire_f, wire_b, inbox_f, inbox_b, saved,
                 gacc, hacc, dxacc, lacc) = carry
            fm = ftbl[k][stage]
            bm = btbl[k][stage]
            afk = af[k][stage]
            abk = ab[k][stage]

            # bank last tick's arrivals under their microbatch slot
            inbox_f = jax.lax.cond(
                afk >= 0,
                lambda ib: jax.lax.dynamic_update_index_in_dim(
                    ib, wire_f, afk % S, 0),
                lambda ib: ib, inbox_f)
            inbox_b = jax.lax.cond(
                abk >= 0,
                lambda ib: jax.lax.dynamic_update_index_in_dim(
                    ib, wire_b, abk % S, 0),
                lambda ib: ib, inbox_b)

            # ---- forward phase -------------------------------------
            def fwd_branch(op):
                saved, = op
                h_in = jnp.where(
                    stage == 0, x_mb[jnp.clip(fm, 0, n_micro - 1)],
                    inbox_f[fm % S])
                h_out = _stage(params, h_in, fm)
                saved = jax.lax.dynamic_update_index_in_dim(
                    saved, h_in, fm % S, 0)
                return saved, h_out

            saved, send_f = jax.lax.cond(
                fm >= 0, fwd_branch, lambda op: (op[0], zero_act), (saved,))

            # ---- backward phase ------------------------------------
            def bwd_branch(op):
                if head_params is None:
                    gacc, lacc = op
                else:
                    gacc, hacc, dxacc, lacc = op
                m_clip = jnp.clip(bm, 0, n_micro - 1)
                h_in = saved[bm % S]
                h_out, pull = jax.vjp(
                    lambda p, h: _stage(p, h, bm), params, h_in)
                if head_params is None:
                    loss_m, lpull = jax.vjp(
                        lambda ho: loss_fn(ho, y_mb[m_clip]), h_out)
                    (dh_loss,) = lpull(jnp.ones_like(loss_m))
                    loss_add = jnp.where(stage == S - 1,
                                         loss_m.astype(jnp.float32), 0.0)
                else:
                    # the head (final norm + vocab projection) runs only
                    # where it exists — interior stages skip its FLOPs
                    def at_tail(_):
                        loss_m, lpull = jax.vjp(
                            lambda hp, ho: loss_fn(hp, ho, y_mb[m_clip]),
                            hparams, h_out)
                        dhp, dh = lpull(jnp.ones_like(loss_m))
                        return loss_m.astype(jnp.float32), dhp, dh

                    def not_tail(_):
                        return (jnp.float32(0),
                                jax.tree_util.tree_map(jnp.zeros_like,
                                                       hparams),
                                jnp.zeros_like(h_out))

                    loss_add, dhp, dh_loss = jax.lax.cond(
                        stage == S - 1, at_tail, not_tail, None)
                    hacc = jax.tree_util.tree_map(jnp.add, hacc, dhp)
                g_in = jnp.where(stage == S - 1, dh_loss,
                                 inbox_b[bm % S])
                dp, dh_in = pull(g_in)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, dp)
                lacc = lacc + loss_add
                if head_params is None:
                    return gacc, lacc, dh_in
                # stage 0's incoming cotangent IS d(loss)/d(x_mb[m])
                dxacc = jax.lax.dynamic_update_index_in_dim(
                    dxacc,
                    jnp.where(stage == 0, dh_in, jnp.zeros_like(dh_in)),
                    m_clip, 0)
                return gacc, hacc, dxacc, lacc, dh_in

            if head_params is None:
                gacc, lacc, send_b = jax.lax.cond(
                    bm >= 0, bwd_branch,
                    lambda op: (op[0], op[1], zero_act), (gacc, lacc))
            else:
                gacc, hacc, dxacc, lacc, send_b = jax.lax.cond(
                    bm >= 0, bwd_branch,
                    lambda op: (op[0], op[1], op[2], op[3], zero_act),
                    (gacc, hacc, dxacc, lacc))

            # collectives OUTSIDE the conds: every device participates
            wire_f = jax.lax.ppermute(send_f, axis, perm_f)
            wire_b = jax.lax.ppermute(send_b, axis, perm_b)
            if head_params is None:
                return (wire_f, wire_b, inbox_f, inbox_b, saved,
                        gacc, lacc), None
            return (wire_f, wire_b, inbox_f, inbox_b, saved,
                    gacc, hacc, dxacc, lacc), None

        gacc0 = jax.tree_util.tree_map(jnp.zeros_like, params)

        def _dp_mean(v):
            # mean over dp rows: the global loss is the mean of per-row
            # slice losses, so row grads/losses scale by 1/dpn and sum
            return jax.lax.psum(v, dp_ax) / dpn if dp_ax else v

        if head_params is None:
            carry0 = (zero_act, zero_act, ring0, ring0, ring0,
                      gacc0, jnp.float32(0))
            (*_, gacc, lacc), _ = jax.lax.scan(tick, carry0,
                                               jnp.arange(T))
            loss = _dp_mean(jax.lax.psum(lacc, axis)) / n_micro
            grads = jax.tree_util.tree_map(
                lambda g: (_dp_mean(g) / n_micro)[None], gacc)
            return loss, grads
        hacc0 = jax.tree_util.tree_map(jnp.zeros_like, hparams)
        dx0 = jnp.zeros((n_micro,) + act_shape, dt)
        carry0 = (zero_act, zero_act, ring0, ring0, ring0,
                  gacc0, hacc0, dx0, jnp.float32(0))
        (*_, gacc, hacc, dxacc, lacc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T))
        loss = _dp_mean(jax.lax.psum(lacc, axis)) / n_micro
        grads = jax.tree_util.tree_map(
            lambda g: (_dp_mean(g) / n_micro)[None], gacc)
        # head grads live only at the tail, dx only at stage 0 — psum
        # replicates both to every stage
        hgrads = jax.tree_util.tree_map(
            lambda g: _dp_mean(jax.lax.psum(g, axis)) / n_micro, hacc)
        # the sweep seeds each microbatch loss with cotangent 1; the
        # returned total is the MEAN over microbatches, so dx needs the
        # same 1/n_micro the stage/head grads get
        # dx stays SHARDED over dp (each row's slice cotangent) but
        # scales by 1/dpn like everything else differentiating the mean
        dx = jax.lax.psum(dxacc, axis) / n_micro / dpn
        return loss, grads, hgrads, dx

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    bspec = P(None, dp_ax)          # (n_micro, batch/dp, ...)
    if head_params is None:
        loss, grads = _shard_map(
            local, mesh, in_specs=(pspec, bspec, bspec),
            out_specs=(P(), pspec))(stage_params, x_mb, y_mb)
        return loss, grads
    hspec = jax.tree_util.tree_map(lambda _: P(), head_params)
    loss, grads, hgrads, dx = _shard_map(
        lambda sp, xm, ym, hp: local(sp, xm, ym, hp),
        mesh, in_specs=(pspec, bspec, bspec, hspec),
        out_specs=(P(), pspec, hspec, bspec))(
            stage_params, x_mb, y_mb, head_params)
    dx = dx.reshape((B,) + x.shape[1:])
    return loss, grads, hgrads, dx


# ---------------------------------------------------------------------------
# Real-model pipeline parallelism: GPT blocks as pipeline stages
# ---------------------------------------------------------------------------

from .spmd import PartitionRules  # noqa: E402  (no gluon<->parallel cycle)
from ..gluon.block import HybridBlock  # noqa: E402

PIPELINE_RULES = PartitionRules([
    # stacked per-stage block weights: leading (stage) dim over pp
    (r"stage_", P("pp")),
])


class GPTPipe(HybridBlock):
    """GPT whose transformer blocks run as GPipe pipeline stages.

    Beyond-reference capability (SURVEY.md 2.3: PP absent upstream) on a
    REAL model: the per-block weights live as stacked ``(num_layers, ...)``
    parameters sharded over the mesh's ``pp`` axis (PIPELINE_RULES), and
    forward streams microbatches through ONE template :class:`GPTBlock`
    whose buffers are rebound per stage (``_bind_params``) inside
    :func:`pipeline_apply` — the block math is the model zoo's own, not a
    reimplementation. Works under SPMDTrainer (the stacked params are
    ordinary Parameters).

    In-pipeline dropout (r3): a per-(microbatch, stage) PRNG key threads
    through the schedule (``pipeline_apply(rng_key=...)``), scoped around
    the template block so its dropout ops draw fresh randomness each
    microbatch at each stage — and regenerate identically in the
    backward recompute.
    """

    def __init__(self, mesh, vocab_size: int = 50257, num_layers: int = 4,
                 units: int = 256, hidden_size: int = 1024,
                 num_heads: int = 4, max_length: int = 512,
                 num_microbatches: Optional[int] = None,
                 axis: str = "pp", dropout: float = 0.0,
                 schedule: str = "gpipe",
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        from ..gluon.model_zoo.gpt import GPTBlock
        from ..gluon.nn import Embedding, LayerNorm
        from ..gluon.parameter import Parameter

        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"schedule must be 'gpipe' or '1f1b', "
                             f"got {schedule!r}")
        if schedule == "1f1b":
            # r5: dp composes (the sweep shards the microbatch batch dim
            # over dp and psums grads/loss — pipeline_train_grads).
            # Other axes (tp/sp) would still be silently replicated: the
            # sweep's stage math carries no in-stage sharding rules.
            extra = [a for a in mesh.axis_names
                     if a not in (axis, "dp") and mesh.shape[a] > 1]
            if extra:
                raise ValueError(
                    f"schedule='1f1b' supports a {axis}(+dp) mesh; "
                    f"axes {extra} would be silently replicated — use "
                    "schedule='gpipe' to compose pp with tp/sp")
        # '1f1b': SPMDTrainer routes gradients through the hand-scheduled
        # sweep (pipeline_loss_and_grads) — S-slot residual memory and
        # tail-ramp backward overlap instead of GPipe's M-microbatch
        # footprint. Inference/forward always uses the GPipe schedule
        # (forward-only has no backward to overlap).
        self.schedule = schedule
        self._mesh = mesh
        self._axis = axis
        self._n_micro = num_microbatches
        self._units = units
        self._max_length = max_length
        self._num_layers = num_layers
        self._dropout = float(dropout)

        self.word_embed = Embedding(vocab_size, units)
        self.position_weight = Parameter(
            "position_weight", shape=(max_length, units), init="normal")
        self.ln_f = LayerNorm(epsilon=1e-5, in_channels=units)

        # template block: supplies the stage math; its own (tiny) buffers
        # are bind targets only, never trained — bypass child registration
        tpl = GPTBlock(units, hidden_size, num_heads, dropout=dropout)
        tpl.initialize()
        object.__setattr__(self, "_template", tpl)
        tpl_params = list(tpl.collect_params().items())
        object.__setattr__(self, "_tpl_params",
                           [p for _, p in tpl_params])
        for name, p in tpl_params:
            sp = Parameter("stage_" + name.replace(".", "_"),
                           shape=(num_layers,) + tuple(p.shape),
                           init=getattr(p, "init", None) or "uniform")
            setattr(self, "stage_" + name.replace(".", "_"), sp)
        object.__setattr__(
            self, "_stacked",
            [getattr(self, "stage_" + name.replace(".", "_"))
             for name, _ in tpl_params])

    def load_block_weights(self, gpt_model) -> None:
        """Copy a :class:`GPTModel`'s per-block weights into the stacked
        stage parameters (for parity tests / converting a trained model)."""
        from ..ndarray.ndarray import NDArray
        blocks = list(gpt_model.blocks._children.values())
        assert len(blocks) == self._num_layers, \
            (len(blocks), self._num_layers)
        per_block = [list(b.collect_params().values()) for b in blocks]
        for k, sp in enumerate(self._stacked):
            stacked = jnp.stack(
                [per_block[i][k].data()._data
                 for i in range(self._num_layers)])
            sp.set_data(NDArray(stacked))

    def _mesh_place(self, nd, spec):
        """Commit an NDArray's buffer to this mesh (writes back), or pass
        tracers through untouched."""
        arr = nd._data
        if isinstance(arr, jax.core.Tracer):
            return arr
        sh = jax.sharding.NamedSharding(self._mesh, spec)
        cur = getattr(arr, "sharding", None)
        if cur is not None and (cur == sh or (
                hasattr(cur, "is_equivalent_to") and
                cur.is_equivalent_to(sh, arr.ndim))):
            return arr
        arr = jax.device_put(arr, sh)
        nd._data = arr
        from ..ndarray.register import mark_mesh_resident
        if sh.num_devices > 1:
            mark_mesh_resident(nd)   # wrapper outlives per-step buffers
        return arr

    def forward(self, tokens):
        from ..gluon.block import _bind_params
        from ..ndarray.ndarray import from_jax
        from ..ndarray import ops
        from .. import numpy as mxnp
        # eager ops downstream of the pipeline mix mesh-sharded activations
        # with single-device params; the per-op harmonization scan engages
        # via mark_mesh_resident on each placed buffer (and disengages when
        # the last one is collected)
        T = tokens.shape[1]
        if not self.position_weight.is_initialized:
            self.position_weight._finish_deferred_init(
                (self._max_length, self._units))
        x = self.word_embed(tokens)
        pos = ops.slice_axis(self.position_weight.data(), axis=0,
                             begin=0, end=T)
        x = x + pos.expand_dims(0)

        tpl = self._template
        tpl_params = self._tpl_params

        def stage_fn(param_slices, h, key=None):
            from ..ndarray import random as _random
            with _bind_params(tpl_params, param_slices):
                if key is None:
                    out = tpl.forward(from_jax(h))
                else:
                    # scope the per-(microbatch, stage) key so the
                    # block's dropout ops draw from it
                    with _random.trace_key_scope(key):
                        out = tpl.forward(from_jax(h))
            return out._data

        # eager path: stacked weights must live sharded over the pp mesh
        # (write back so the placement is paid once); tracers are already
        # placed by the enclosing pjit (SPMDTrainer rules)
        arrays = []
        for p in self._stacked:
            nd = p.data()
            arrays.append(self._mesh_place(nd, P(self._axis)))
        # pp composes with dp when the mesh has one: activations shard
        # their batch dim over dp, each dp row pipelines its own slice
        bax = "dp" if "dp" in self._mesh.axis_names else None
        h = self._mesh_place(x, P(bax))
        rng = None
        from .._tape import is_training
        if self._dropout > 0.0 and is_training():
            from ..ndarray import random as _random
            rng = _random.split_key()
        out = pipeline_apply(stage_fn, arrays, h, self._mesh,
                             axis=self._axis,
                             num_microbatches=self._n_micro,
                             rng_key=rng, batch_axis=bax)
        if not isinstance(out, jax.core.Tracer) \
                and getattr(out, "sharding", None) is not None \
                and out.sharding.num_devices > 1:
            from ..ndarray.register import mark_mesh_resident
            mark_mesh_resident(out)
        x = self.ln_f(from_jax(out))
        w = self.word_embed.weight.data()
        return mxnp.matmul(x, w.T)

    def pipeline_loss_and_grads(self, params, param_arrays, inputs,
                                labels, loss_fn, rng=None,
                                output_transform=None):
        """SPMDTrainer gradient hook (``schedule='1f1b'``): full-model
        loss and per-parameter grads through the hand-scheduled 1F1B
        sweep — the embedding runs (and backprops) OUTSIDE the pipeline
        via ``jax.vjp`` chained on the sweep's ``dx``, the final norm +
        tied LM projection run INSIDE it as last-stage head params.
        Returns ``(loss, grads, mutated={})`` with grads aligned to
        ``param_arrays``."""
        from ..gluon.block import _bind_params
        from ..ndarray.ndarray import from_jax

        tokens = inputs[0]
        T = int(tokens.shape[1])
        idx = {id(p): i for i, p in enumerate(params)}

        def arr(p):
            return param_arrays[idx[id(p)]]

        ew = arr(self.word_embed.weight)
        pw = arr(self.position_weight)
        ln_plist = list(self.ln_f.collect_params().values())
        ln_arrays = tuple(arr(p) for p in ln_plist)
        stage_arrays = [arr(sp) for sp in self._stacked]

        def embed_fn(ew_, pw_):
            return jnp.take(ew_, tokens, axis=0) + pw_[:T][None]

        x_act, embed_vjp = jax.vjp(embed_fn, ew, pw)

        tpl, tpl_params = self._template, self._tpl_params

        def stage_fn(param_slices, h, key=None):
            from ..ndarray import random as _random
            with _bind_params(tpl_params, param_slices):
                if key is None:
                    out = tpl.forward(from_jax(h))
                else:
                    with _random.trace_key_scope(key):
                        out = tpl.forward(from_jax(h))
            return out._data

        head_params = ln_arrays + (ew,)

        def head_loss(hp, h_out, y_mb):
            with _bind_params(ln_plist, list(hp[:-1])):
                xo = self.ln_f.forward(from_jax(h_out))
            logits = from_jax(jnp.matmul(xo._data, hp[-1].T))
            if output_transform is not None:
                logits = output_transform(logits)
            l = loss_fn(logits, from_jax(y_mb))
            return jnp.mean(l._data)

        from .._tape import is_training
        rng_key = rng if (self._dropout > 0.0 and rng is not None
                          and is_training()) else None
        loss, sgrads, hgrads, dx = pipeline_train_grads(
            stage_fn, head_loss, stage_arrays, x_act, labels, self._mesh,
            axis=self._axis, num_microbatches=self._n_micro,
            rng_key=rng_key, head_params=head_params)
        d_ew_embed, d_pw = embed_vjp(dx)
        grads = [jnp.zeros_like(a) for a in param_arrays]
        # tied embedding: lookup grad + LM-projection grad
        grads[idx[id(self.word_embed.weight)]] = hgrads[-1] + d_ew_embed
        grads[idx[id(self.position_weight)]] = d_pw
        for p, g in zip(ln_plist, hgrads[:-1]):
            grads[idx[id(p)]] = g
        for sp, g in zip(self._stacked, sgrads):
            grads[idx[id(sp)]] = g
        return loss, grads, {}
