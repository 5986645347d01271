"""Transformer attention ops.

Reference parity (leezu/mxnet): ``src/operator/contrib/transformer.{cc,cu}``
— the gluon-nlp BERT-era interleaved self-attention matmuls
(``_contrib_interleaved_matmul_selfatt_qk`` / ``_valatt``) — SURVEY.md
section 2.2. Those exist because cuBLAS wanted one interleaved QKV buffer;
on TPU the fused form is a single ``dot_product_attention`` that XLA maps
onto the MXU (and a Pallas flash kernel for long sequences — see
``mxnet_tpu/ops/pallas/attention.py``). The interleaved API is provided
for source parity and lowers to the same fused path.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .._tape import is_training
from ..base import getenv, register_env
from ..ndarray.ndarray import NDArray
from ..ndarray.ops import _as_nd
from ..ndarray.register import invoke, register_op

__all__ = ["dot_product_attention", "multi_head_attention",
           "interleaved_matmul_selfatt_qk", "interleaved_matmul_selfatt_valatt",
           "interleaved_matmul_encdec_qk", "interleaved_matmul_encdec_valatt"]

register_env("MXNET_ATTENTION_USE_PALLAS", 0,
             "Force the Pallas flash-attention kernel on every sequence "
             "length (it auto-engages from MXNET_FLASH_MIN_SEQ up).")
register_env("MXNET_FLASH_MIN_SEQ", 512,
             "Sequence length at/above which attention auto-routes to "
             "the Pallas flash kernel (the measured v5e crossover vs "
             "XLA materialized-scores attention).")
register_env("MXNET_FLASH_BLOCK_Q", 0,
             "Flash-attention query-block rows. 0 (default) = "
             "shape-aware auto: the FULL sequence as one block at "
             "T<=512 (one grid row per head — measured +5.5% BERT-base "
             "step throughput vs 256-row blocks at T=512), 256-row "
             "blocks (the r3 attn_probe sweep's pick; the probe is in "
             "git history before PR 30) from T=1024 up.")
register_env("MXNET_FLASH_BLOCK_K", 1024,
             "Flash-attention key-block rows (v5e-tuned default; "
             "clamped to the sequence length per call).")


def _mask_to_bias(mask, dtype, batch: int, tq: int, tk: int):
    """Normalize a mask to an additive bias of rank 4 (B/1, H/1, Tq/1, Tk).

    Accepted shapes: (B, Tk) key-padding mask (the canonical BERT
    valid-length mask), (Tq, Tk) score mask, (B, Tq, Tk), or rank-4
    (B/1, H/1, Tq/1, Tk). Boolean True = attend.
    """
    if mask.dtype == jnp.bool_:
        bias = jnp.where(mask, jnp.asarray(0.0, dtype),
                         jnp.finfo(dtype).min)
    else:
        bias = mask
    if bias.ndim == 2:
        if bias.shape == (batch, tk) and (batch != tq or tq == tk):
            bias = bias[:, None, None, :]      # key-padding: (B,1,1,Tk)
        else:
            bias = bias[None, None, :, :]      # score mask: (1,1,Tq,Tk)
    elif bias.ndim == 3:
        bias = bias[:, None, :, :]             # (B,1,Tq,Tk)
    return bias


def dot_product_attention(query, key, value, mask=None,
                          scale: Optional[float] = None,
                          dropout: float = 0.0, causal: bool = False):
    """Fused scaled dot-product attention.

    Shapes: (B, T, H, D) for q/k/v (jax convention — batch, time, heads,
    head_dim). Returns (B, T, H, D). Uses XLA's fused attention; the
    Pallas flash kernel (ops/pallas/attention.py) engages on TPU for long
    sequences or when MXNET_ATTENTION_USE_PALLAS=1.
    """
    inputs = [_as_nd(query), _as_nd(key), _as_nd(value)]
    has_mask = mask is not None
    if has_mask:
        inputs.append(_as_nd(mask))
    # training flag and RNG draw resolve OUTSIDE impl: the per-op exec
    # cache would otherwise bake both into the compiled program (stale
    # dropout mode; one frozen mask reused every step) — the seed rides
    # as an op INPUT so every call gets fresh randomness
    train_rate = float(dropout) if is_training() else 0.0
    if train_rate > 0.0:
        inputs.append(_as_nd(_attn_seed()))
    sc, cz = scale, causal
    # env-dependent routing resolves OUTSIDE impl so it lands in the
    # closure cells the per-op exec cache keys on — toggling
    # MXNET_ATTENTION_USE_PALLAS / MXNET_FLASH_BLOCK_* at runtime must
    # re-dispatch, not silently hit a stale executable
    use_flash = _use_pallas_len(inputs[0].shape[1])
    blk_q = _flash_block("Q", seq=inputs[0].shape[1])
    blk_k = _flash_block("K")

    def attend(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if train_rate > 0.0 else None
        bias = None
        mask_learned = False
        if rest:
            bias = _mask_to_bias(rest[0], q.dtype, q.shape[0], q.shape[1],
                                 k.shape[1])
            mask_learned = rest[0].dtype != jnp.bool_
        ring = _use_ring(q, k)
        if ring is not None and _ring_bias_ok(bias, q, k):
            # padding masks and dropout stay ON the ring path (r3): the
            # bias row-stripe shards with q, dropout masks regenerate
            # per (shard, block)
            from ..parallel.ring import ring_attention
            mesh, axis = ring
            return ring_attention(q, k, v, mesh, axis=axis,
                                  scale=sc, causal=cz, bias=bias,
                                  dropout=train_rate, dropout_seed=seed)
        if use_flash and _flash_bias_ok(bias, q, k):
            return _flash(q, k, v, bias, seed, scale=sc, causal=cz,
                          block_q=blk_q, block_k=blk_k,
                          dropout=train_rate, bias_grad=mask_learned)
        if train_rate > 0.0:
            from .pallas.attention import dense_dropout_attention_bhtd
            import math as _math
            s = sc if sc is not None else 1.0 / _math.sqrt(q.shape[-1])
            qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
            out = dense_dropout_attention_bhtd(
                qt, kt, vt, bias, seed, train_rate, float(s), bool(cz))
            return jnp.swapaxes(out, 1, 2)
        return jax.nn.dot_product_attention(
            q, k, v, bias=bias, scale=sc, is_causal=cz)

    def impl(*arrays):
        with jax.named_scope("attn/core"):
            return attend(*arrays)

    return invoke("dot_product_attention", impl, inputs)


def _flash(q, k, v, bias, seed, *, dropout, **kw):
    """The flash kernel over (B, T, H, D) operands, under whatever mesh
    the step is being compiled for.

    GSPMD cannot partition a Mosaic custom call, so on a multi-device
    mesh (``parallel.mesh.kernel_mesh``, entered by SPMDTrainer) the
    call is shard_mapped: batch over the data axes and heads over ``tp``
    where they divide, every other axis replicated.  Attention is
    independent per (batch, head), so each shard is a whole problem:
    the kernel forms its head groups (``attention.head_group``) from the
    heads its shard holds, so a ``tp`` shard always holds whole groups.
    Inside an enclosing shard_map (ring / pipeline) the operands are
    already per-device and the kernel is called directly."""
    from .pallas.attention import flash_attention
    from ..parallel.mesh import current_kernel_mesh

    def call(q, k, v, bias, seed):
        return flash_attention(q, k, v, bias=bias, dropout=dropout,
                               dropout_seed=seed, **kw)

    km = current_kernel_mesh()
    if km is None or km[0].size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return call(q, k, v, bias, seed)
    mesh, batch_axes = km
    B, H = q.shape[0], q.shape[2]

    def fit(axes, dim):
        keep = tuple(a for a in axes if a in mesh.axis_names)
        n = 1
        for a in keep:
            n *= mesh.shape[a]
        return keep if keep and dim % n == 0 else None

    bax, hax = fit(batch_axes, B), fit(("tp",), H)
    P = jax.sharding.PartitionSpec
    spec = P(bax, None, hax, None)
    args, in_specs = [q, k, v], [spec, spec, spec]
    if bias is not None:            # (B|1, H|1, Tq|1, Tk)
        args.append(bias)
        in_specs.append(P(bax if bias.shape[0] > 1 else None,
                          hax if bias.shape[1] > 1 else None, None, None))
    has_seed = seed is not None
    if has_seed:
        args.append(seed)
        in_specs.append(P())

    def local(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if has_seed else None
        if has_seed:
            # the kernel seeds each tile from (seed, b, h, iq, ik) with
            # LOCAL b/h: shift by this shard's global offsets so shards
            # draw the masks the unsharded kernel would, not each other's
            b0 = _shard_offset(mesh, bax, q.shape[0])
            h0 = _shard_offset(mesh, hax, q.shape[2])
            from .pallas.attention import dropout_seed_at
            seed = dropout_seed_at(seed, b0, h0)
        return call(q, k, v, rest[0] if rest else None, seed)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=spec, check_vma=False)(*args)


def _shard_offset(mesh, axes, local_size: int):
    """Global start index of this shard along a dim sharded over
    ``axes`` (major-to-minor, as PartitionSpec orders them)."""
    idx = 0
    for a in axes or ():
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx * local_size


def _flash_block(which: str, seq: int = 0) -> int:
    from .pallas.attention import DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    env = int(getenv(f"MXNET_FLASH_BLOCK_{which}", 0))
    if env:
        return env
    if which == "Q":
        # shape-aware default: at T<=512 one query block per (batch,
        # head group) removes per-block grid overhead; at 1024+ 256-row
        # blocks win (measured again in PR 36, BERT-large b16xT=512
        # forward + backward: 0.890 ms a layer whole, 0.998 at 256; the
        # sweep is beside attention.DEFAULT_BLOCK_Q).
        if 0 < seq <= 512:
            return seq
        return DEFAULT_BLOCK_Q
    return DEFAULT_BLOCK_K


def _flash_bias_ok(bias, q, k) -> bool:
    """The Pallas kernel broadcasts bias over dims 0/1 and (r3) over a
    unit query dim — (B,1,1,Tk) key-padding masks, the canonical BERT
    case, stream as per-tile rows. Only the trailing key dim must be
    full-size."""
    if bias is None:
        return True
    return (bias.ndim == 4 and bias.shape[2] in (1, q.shape[1]) and
            bias.shape[3] == k.shape[1])


def _attn_seed():
    """(2,) int32 seed from the framework RNG stream; under a hybridize
    trace this rides the threaded threefry key, so compiled programs get
    fresh dropout per step."""
    from ..ndarray import random as _random
    key = _random.split_key()
    return jax.random.key_data(key).reshape(-1)[:2].astype(jnp.int32)


# Ring attention shards bias rows with q and slices columns per ring
# step — the SAME (B|1, H|1, 1|Tq, Tk) contract as the flash kernel.
_ring_bias_ok = _flash_bias_ok


def _use_ring(q, k):
    """Sequence-parallel policy: a sequence_parallel context is active and
    the sequence divides over the axis → (mesh, axis), else None."""
    from ..parallel.ring import current_sequence_parallel
    sp = current_sequence_parallel()
    if sp is None:
        return None
    mesh, axis = sp
    if axis not in mesh.axis_names:
        return None
    n = mesh.shape[axis]
    if n <= 1 or q.shape[1] % n or k.shape[1] % n:
        return None
    return mesh, axis


def _use_pallas(q) -> bool:
    """Pallas flash kernel policy: explicit opt-in, or long sequences on
    TPU where the O(T^2) materialized-scores path thrashes HBM."""
    return _use_pallas_len(q.shape[1])


def _flash_threshold() -> int:
    """Sequence length at/above which the Pallas flash kernel beats XLA's
    materialized-scores attention. Measured crossover on v5e (r3 kernel:
    input-dtype MXU matmuls, causal tile skip, grid semantics): GPT-2
    tok/s pallas-vs-xla is 104k/115k at T=256, 101k/97k at 512,
    94k/71k at 1024, 81k/50k at 2048 — flash wins from 512 up.

    r5: the backward IS now a fused single pass whenever Tk fits one
    k-block (every T <= MXNET_FLASH_BLOCK_K=1024 — all headline
    shapes), halving kernel launches/q-k-v reads/probability
    recomputes.  Measured effect (attn_probe, in git history before
    PR 30; b32 h12 d64, 60-iter scan, fwdbwd ms/step, flash uses
    256x1024 blocks clamped to T):

        T      xla    flash(fused)   flash(two-pass, bk=T/2)
        128    1.79      2.26              —
        256    2.11      2.78             3.59
        512    5.60      4.68             6.30
        1024  17.51      8.64            12.96

    Fused is 26-33 percent faster than the two-pass recipe at equal shapes,
    flipping T=512 from marginal to +16 percent over XLA and widening T=1024
    to 2x; it also lifted BERT b48x512 train by +3.9 percent.  T <= 256
    STAYS on XLA: both paths are latency-floored there (2-6 TFLOP/s on
    a 193 TFLOP/s chip — the op can't fill the MXU at any kernel
    structure), and XLA's single fused program has the smaller fixed
    cost.  The crossover therefore remains 512 — measured, not
    assumed; the auto-threshold keeps every config on its faster
    path."""
    return int(getenv("MXNET_FLASH_MIN_SEQ", 512))


def _use_pallas_len(seq_len: int) -> bool:
    if getenv("MXNET_ATTENTION_USE_PALLAS", 0):
        return True
    return jax.default_backend() != "cpu" and \
        seq_len >= _flash_threshold()


def multi_head_attention(query, key, value, num_heads: int, mask=None,
                         causal: bool = False, scale: Optional[float] = None,
                         dropout: float = 0.0):
    """(B, T, C) inputs already projected; splits heads, attends, merges.
    ``dropout`` is attention-probability dropout (training mode only)."""
    nh, cz, sc = num_heads, causal, scale
    inputs = [_as_nd(query), _as_nd(key), _as_nd(value)]
    has_mask = mask is not None
    if has_mask:
        inputs.append(_as_nd(mask))
    # resolved outside impl — see dot_product_attention
    train_rate = float(dropout) if is_training() else 0.0
    if train_rate > 0.0:
        inputs.append(_as_nd(_attn_seed()))
    # resolved outside impl (exec-cache closure token) — see
    # dot_product_attention
    use_flash = _use_pallas_len(inputs[0].shape[1])
    blk_q = _flash_block("Q", seq=inputs[0].shape[1])
    blk_k = _flash_block("K")

    def attend(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if train_rate > 0.0 else None
        B, Tq, C = q.shape
        Tk = k.shape[1]
        d = C // nh
        qh = q.reshape(B, Tq, nh, d)
        kh = k.reshape(B, Tk, nh, d)
        vh = v.reshape(B, Tk, nh, d)
        bias = None
        mask_learned = False
        if rest:
            bias = _mask_to_bias(rest[0], q.dtype, B, Tq, Tk)
            mask_learned = rest[0].dtype != jnp.bool_
        ring = _use_ring(qh, kh)
        if ring is not None and _ring_bias_ok(bias, qh, kh):
            from ..parallel.ring import ring_attention
            mesh, axis = ring
            out = ring_attention(qh, kh, vh, mesh, axis=axis,
                                 scale=sc, causal=cz, bias=bias,
                                 dropout=train_rate, dropout_seed=seed)
        elif use_flash and _flash_bias_ok(bias, qh, kh):
            out = _flash(qh, kh, vh, bias, seed, scale=sc, causal=cz,
                         block_q=blk_q, block_k=blk_k,
                         dropout=train_rate, bias_grad=mask_learned)
        elif train_rate > 0.0:
            from .pallas.attention import dense_dropout_attention_bhtd
            import math as _math
            s = sc if sc is not None else 1.0 / _math.sqrt(d)
            out = jnp.swapaxes(dense_dropout_attention_bhtd(
                jnp.swapaxes(qh, 1, 2), jnp.swapaxes(kh, 1, 2),
                jnp.swapaxes(vh, 1, 2), bias, seed, train_rate,
                float(s), bool(cz)), 1, 2)
        else:
            out = jax.nn.dot_product_attention(qh, kh, vh, bias=bias,
                                               scale=sc, is_causal=cz)
        return out.reshape(B, Tq, C)

    def impl(*arrays):
        with jax.named_scope("attn/core"):
            return attend(*arrays)

    return invoke("multi_head_attention", impl, inputs)


# ---------------------------------------------------------------------------
# Interleaved-QKV API parity (reference transformer.cc). Layout matches the
# reference: qkv is (T, N, 3*H*D) with per-head interleaving [q|k|v].
# ---------------------------------------------------------------------------

def interleaved_matmul_selfatt_qk(queries_keys_values, heads: int):
    """scores = scaled Q·Kᵀ from interleaved QKV, out (N*heads, T, T)."""
    nh = heads

    def impl(qkv):
        T, N, C3 = qkv.shape
        d = C3 // (3 * nh)
        x = qkv.reshape(T, N, nh, 3, d)
        q = x[:, :, :, 0]  # (T, N, H, D)
        k = x[:, :, :, 1]
        q = jnp.transpose(q, (1, 2, 0, 3)).reshape(N * nh, T, d)
        k = jnp.transpose(k, (1, 2, 0, 3)).reshape(N * nh, T, d)
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
        return jnp.einsum("btd,bsd->bts", q * scale, k)

    return invoke("interleaved_matmul_selfatt_qk", impl,
                  (_as_nd(queries_keys_values),))


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                      heads: int):
    """out = att·V back to (T, N, H*D) from interleaved QKV."""
    nh = heads

    def impl(qkv, att):
        T, N, C3 = qkv.shape
        d = C3 // (3 * nh)
        x = qkv.reshape(T, N, nh, 3, d)
        v = x[:, :, :, 2]
        v = jnp.transpose(v, (1, 2, 0, 3)).reshape(N * nh, T, d)
        out = jnp.einsum("bts,bsd->btd", att, v)  # (N*H, T, D)
        out = out.reshape(N, nh, T, d)
        return jnp.transpose(out, (2, 0, 1, 3)).reshape(T, N, nh * d)

    return invoke("interleaved_matmul_selfatt_valatt", impl,
                  (_as_nd(queries_keys_values), _as_nd(attention)))


def interleaved_matmul_encdec_qk(queries, keys_values, heads: int):
    nh = heads

    def impl(q, kv):
        Tq, N, C = q.shape
        Tk = kv.shape[0]
        d = C // nh
        qh = jnp.transpose(q.reshape(Tq, N, nh, d), (1, 2, 0, 3)) \
            .reshape(N * nh, Tq, d)
        k = kv.reshape(Tk, N, nh, 2, d)[:, :, :, 0]
        kh = jnp.transpose(k, (1, 2, 0, 3)).reshape(N * nh, Tk, d)
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
        return jnp.einsum("btd,bsd->bts", qh * scale, kh)

    return invoke("interleaved_matmul_encdec_qk", impl,
                  (_as_nd(queries), _as_nd(keys_values)))


def interleaved_matmul_encdec_valatt(keys_values, attention, heads: int):
    nh = heads

    def impl(kv, att):
        Tk, N, C2 = kv.shape
        d = C2 // (2 * nh)
        v = kv.reshape(Tk, N, nh, 2, d)[:, :, :, 1]
        vh = jnp.transpose(v, (1, 2, 0, 3)).reshape(N * nh, Tk, d)
        out = jnp.einsum("bts,bsd->btd", att, vh)
        Tq = att.shape[1]
        out = out.reshape(N, nh, Tq, d)
        return jnp.transpose(out, (2, 0, 1, 3)).reshape(Tq, N, nh * d)

    return invoke("interleaved_matmul_encdec_valatt", impl,
                  (_as_nd(keys_values), _as_nd(attention)))


for _name in __all__:
    register_op(_name, globals()[_name])
