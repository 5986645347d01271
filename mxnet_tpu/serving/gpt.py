"""GPTDecodeModel — the decode path of the GPT family
(``gluon.model_zoo.gpt.GPTModel``): pre-LN blocks of full multi-head
attention and a dense GELU FFN, learned positions, a tied head.

What a slot holds: ``rows`` in every layer, K and V ``(S, heads * d,
L)`` in the bucket grid (``PagedKVCache``).  Prefill is one program a
prompt bucket from the zoo's block functions.  The decode step is one
donated program a KV bucket over every slot; it writes each slot's K/V
column with one ``dynamic_update_slice`` a slot and buffer and reads the
whole bucket, masked past each slot's position.

Rows are all a slot holds, so this family can rewind a slot and share
a prefix: it has the two programs the other families refuse, a verify
pass over ``k + 1`` tokens a slot (speculation) and a suffix prefill
over resident prefix rows (the prefix cache).  The step, the verify
pass and the draft chain of ``serving.speculation`` all run one step
body, :func:`_logits_step`, a token a column.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError
from .. import metrics as _metrics
from .. import tracing as _tracing
from .kv_cache import PagedKVCache
from .model import DecodeModel, _select_tokens

__all__ = ["GPTDecodeModel"]


def _slot_block_step(p, x, ck, cv, pos, nh: int, ga):
    """One decode token for EVERY slot: ``x`` (S, 1, C), caches
    (S, C, L) — heads and head dim on one axis, positions last: the
    order the device stores them in (``kv_cache`` module docstring) —
    ``pos`` (S,) int32: the per-slot-position variant of
    ``model_zoo.generation._block_step`` (which shares one scalar
    position across the batch; continuous batching cannot)."""
    import math as _math
    import jax
    import jax.numpy as jnp
    from jax import lax

    gelu_approx, eps = ga
    S, _, C = x.shape
    d = C // nh
    L = ck.shape[2]
    with jax.named_scope("attn/qkv"):
        h = _pure_ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        qh = q.reshape(S, 1, nh, d)
        kcol = k.reshape(S, C, 1)
        vcol = v.reshape(S, C, 1)
    # slot i writes its k/v column at ITS position pos[i]: one in-place
    # dynamic_update_slice a slot, NOT a scatter — a TPU scatter wants
    # its update window on the minor axes, so XLA would relayout the
    # whole buffer to [S][L][C] and back around it, every layer, every
    # token (PERF.md: the positions-last cache).  A position past L-1
    # clamps onto row L-1 where the scatter dropped it; only a verify
    # pass at the grid's top gets there, for rows no emitted token
    # reads (the submit-time budget check)
    with jax.named_scope("cache/write"):
        for i in range(S):
            # positions are never negative: without the flag every traced
            # index gets a wrap-around select, slots x 2 x layers times a
            # program, a second of tracing on every start
            at = (i, 0, lax.index_in_dim(pos, i, keepdims=False))
            ck = lax.dynamic_update_slice(
                ck, lax.slice_in_dim(kcol, i, i + 1), at,
                allow_negative_indices=False)
            cv = lax.dynamic_update_slice(
                cv, lax.slice_in_dim(vcol, i, i + 1), at,
                allow_negative_indices=False)
    with jax.named_scope("attn/core"):
        # the heads' view of the buffers is free: d (64) is whole sublane
        # tiles, so splitting C moves nothing
        kh = ck.reshape(S, nh, d, L)
        vh = cv.reshape(S, nh, d, L)
        scores = jnp.einsum("sqhd,shdk->shqk", qh, kh) / _math.sqrt(d)
        # slot i sees cache positions 0..pos[i] (its prompt + its decoded
        # tokens); pad garbage beyond pos[i] stays invisible until the loop
        # overwrites it position by position
        visible = jnp.arange(L)[None, :] <= pos[:, None]          # (S, L)
        scores = jnp.where(visible[:, None, None, :], scores,
                           jnp.float32(-jnp.inf).astype(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("shqk,shdk->sqhd", probs, vh).reshape(S, 1, C)
    with jax.named_scope("attn/out"):
        x = x + (out @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _pure_ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        return x + (ffn @ p["f2_w"].T + p["f2_b"]), ck, cv


def _pure_ln(x, g, b, eps):
    import jax.numpy as jnp
    from jax import lax
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _block_suffix(p, x, pk, pv, q, nh: int, ga):
    """Causal pass over a prompt SUFFIX against resident prefix KV:
    ``x`` (1, Sb, C) embeds suffix tokens at absolute positions
    ``q..q+Sb``, ``pk``/``pv`` (Pb, nh, d) hold the shared prefix's
    rows (valid through traced ``q``; pad garbage past it is masked).
    Returns (x_out, suffix ck/cv (Sb, nh, d)) — the prefix rows are
    already in the cache, only the suffix rows are new."""
    import math as _math
    import jax
    import jax.numpy as jnp

    gelu_approx, eps = ga
    _, T, C = x.shape
    d = C // nh
    Pb = pk.shape[0]
    with jax.named_scope("attn/qkv"):
        h = _pure_ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        qq, kk, vv = jnp.split(qkv, 3, axis=-1)
        qh = qq.reshape(T, nh, d)
        kh = kk.reshape(T, nh, d)
        vh = vv.reshape(T, nh, d)
    with jax.named_scope("attn/core"):
        k_all = jnp.concatenate([pk, kh], axis=0)       # (Pb + T, nh, d)
        v_all = jnp.concatenate([pv, vh], axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qh, k_all) / _math.sqrt(d)
        cols = jnp.arange(Pb + T)
        # suffix position i (absolute q+i) sees: real prefix rows (< q)
        # and suffix rows up to itself (causal); prefix pad garbage in
        # q..Pb stays invisible
        vis = (cols[None, :] < q) | (
            (cols[None, :] >= Pb)
            & (cols[None, :] - Pb <= jnp.arange(T)[:, None]))
        scores = jnp.where(vis[None, :, :], scores,
                           jnp.float32(-jnp.inf).astype(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v_all).reshape(1, T, C)
    with jax.named_scope("attn/out"):
        x = x + (out @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _pure_ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        return x + (ffn @ p["f2_w"].T + p["f2_b"]), kh, vh


def _logits_step(params, ks, vs, toks, pos, nh: int, ga, j=None):
    """One token for every slot through the whole stack: ``toks`` (S,)
    at positions ``pos`` (S,) (``pos + j`` for column ``j`` of a
    multi-token pass), its K/V column written into the per-layer
    ``ks``/``vs``.  Returns (logits (S, V), new ks, new vs)."""
    import jax

    def at():
        # column j's positions, an add at each use (XLA merges them)
        return pos if j is None else pos + j

    with jax.named_scope("embed"):
        x = (params["embed"][toks][:, None, :]
             + params["pos"][at()][:, None, :])
    new_ks, new_vs = [], []
    for p, ck, cv in zip(params["blocks"], ks, vs):
        x, ck, cv = _slot_block_step(p, x, ck, cv, at(), nh, ga)
        new_ks.append(ck)
        new_vs.append(cv)
    with jax.named_scope("head"):
        x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga[1])
        return x[:, 0, :] @ params["embed"].T, new_ks, new_vs


class GPTDecodeModel(DecodeModel):
    """``DecodeModel`` for a ``GPTModel`` with dense FFNs (one built
    with ``moe_experts`` keeps ``MoEDense``'s capacity mask, which drops
    tokens, and is refused)."""

    family = "gpt"
    zoo = "mxnet_tpu.gluon.model_zoo.gpt.GPTModel"
    supports_rollback = True

    @classmethod
    def _collect(cls, block: Any) -> Tuple[Any, Dict[str, Any]]:
        from ..gluon.model_zoo.generation import _collect
        params = _collect(block)
        nh = next(iter(block.blocks._children.values()))._num_heads
        return params, {
            "num_heads": nh, "head_dim": params["embed"].shape[1] // nh,
            "num_layers": len(params["blocks"]),
            "gelu_approx": params.pop("gelu_approx"),
            "ln_eps": params.pop("ln_eps")}

    def __init__(self, params: Any, cfg: Dict[str, Any], max_length: int,
                 name: str) -> None:
        self.ga = (bool(cfg["gelu_approx"]), float(cfg["ln_eps"]))
        super().__init__(params, cfg, max_length, name)
        self.dtype = params["blocks"][0]["qkv_w"].dtype
        self.logits_dtype = self.dtype
        nh, ga = self.num_heads, self.ga

        def _verify(params, ks, vs, toks, pos, seeds, bases, temps,
                    topks, topps, methods):
            # speculative verification: toks (S, K1) int32 — column 0
            # is each slot's last emitted token, columns 1.. the draft
            # proposals; pos (S,) the write position of column 0.  The
            # program is K1 UNROLLED repetitions of the single-token
            # step (the same step body, the same shapes per sub-step,
            # the same selector), each writing its K/V at pos+j and
            # sampling under counter pos+j-base — so the token this
            # pass computes at any position is BIT-IDENTICAL to what
            # the sequential one-token step would have computed there
            # (the byte-identical-streams contract CI pins).  Inputs
            # past the accepted prefix feed garbage forward; the host
            # discards those columns and rolls their KV rows back
            # (PagedKVCache.truncate)
            import jax.numpy as jnp
            outs = []
            for j in range(toks.shape[1]):
                logits, ks, vs = _logits_step(params, ks, vs, toks[:, j],
                                              pos, nh, ga, j)
                outs.append(_select_tokens(logits, seeds, pos, bases,
                                           temps, topks, topps, methods,
                                           j))
            return jnp.stack(outs, axis=1), ks, vs

        def _prefill_sfx(params, pre_ks, pre_vs, toks, q, t0):
            # suffix pass for shared-prefix admissions: pre_ks/pre_vs
            # are the resident prefix rows (Pb, nh, d) per layer, toks
            # (Sb,) the pad-bucketed suffix, q the traced real prefix
            # length, t0 the traced real suffix length.  Returns the
            # last-real-suffix-token logits + the SUFFIX KV rows only
            import jax
            from jax import lax
            Sb = toks.shape[0]
            with jax.named_scope("embed"):
                x = (params["embed"][toks][None]
                     + lax.dynamic_slice_in_dim(params["pos"], q, Sb,
                                                axis=0)[None])
            ks_o, vs_o = [], []
            for p, pk, pv in zip(params["blocks"], pre_ks, pre_vs):
                x, ck, cv = _block_suffix(p, x, pk, pv, q, nh, ga)
                ks_o.append(ck)
                vs_o.append(cv)
            with jax.named_scope("head"):
                x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga[1])
                h = lax.dynamic_slice_in_dim(x[0], t0 - 1, 1, axis=0)[0]
                return h @ params["embed"].T, ks_o, vs_o

        fam = self.family
        self._prefill_sfx_fn = _tracing.program(_prefill_sfx,
                                                "prefill_suffix", fam)
        # the step's donation contract: verify writes k+1 rows into the
        # resident buffers in place; rejected rows are invisible
        # (visibility mask <= pos) until overwritten
        self._verify_fn = _tracing.program(_verify, "verify", fam,
                                           donate_argnums=(1, 2))

    def _model_fns(self) -> Tuple[Any, Any]:
        import jax
        nh, ga = self.num_heads, self.ga

        def _prefill(params, toks, t0):
            # toks (Lp,) int32 (pad tokens after t0), t0 traced scalar;
            # returns (last-real-token logits (V,), ks/vs lists of
            # (Lp, nh, d)) — garbage pad KV past t0 is masked by the
            # decode position mask until overwritten
            from jax import lax
            from ..gluon.model_zoo.generation import _block_prefill
            Lp = toks.shape[0]
            with jax.named_scope("embed"):
                x = params["embed"][toks][None] + params["pos"][None, :Lp]
            ks, vs = [], []
            for p in params["blocks"]:
                x, ck, cv = _block_prefill(p, x, nh, Lp, ga)
                ks.append(ck[0])
                vs.append(cv[0])
            with jax.named_scope("head"):
                x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga[1])
                h = lax.dynamic_slice_in_dim(x[0], t0 - 1, 1, axis=0)[0]
                return h @ params["embed"].T, ks, vs

        def _logits(params, ks, vs, toks, pos):
            return _logits_step(params, ks, vs, toks, pos, nh, ga)

        return _prefill, _logits

    def make_cache(self, max_slots: int, buckets: Sequence[int],
                   prefix_slots: Optional[int] = None,
                   prefix: Any = None) -> PagedKVCache:
        """The slot cache this family decodes over: rows alone."""
        return PagedKVCache(
            self.n_layers, self.num_heads, self.head_dim, max_slots,
            buckets=buckets, dtype=self.dtype, prefix=prefix,
            prefix_slots=prefix_slots)

    def verify(self, cache: Any, tokens: _np.ndarray,
               positions: _np.ndarray,
               sampling: Optional[Sequence[Any]] = None
               ) -> _np.ndarray:
        """One speculative verification pass over every slot:
        ``tokens`` is (S, k+1) int32 — column 0 each slot's last
        emitted token, columns 1.. the k draft proposals — and the
        return is the (S, k+1) int32 target tokens for those
        positions, each bit-identical to what ``step`` would have
        produced sequentially (same kernel math, same counter-PRNG
        lanes).  The cache's buffers gain k+1 rows per slot starting
        at ``positions``; the caller owns acceptance and rolls back
        rejected rows via ``cache.truncate``.  One compiled program
        per (S, bucket, k+1) triple."""
        import jax
        import jax.numpy as jnp
        S = cache.max_slots
        toks = _np.asarray(tokens, _np.int32)
        if toks.ndim != 2 or toks.shape[0] != S or toks.shape[1] < 2:
            raise MXNetError(
                f"verify wants an (S, k+1) token matrix with k >= 1; "
                f"got shape {toks.shape} for {S} slots")
        if sampling is None:
            sampling = self.greedy_sampling(S)
        if not isinstance(sampling[0], jax.Array):
            sampling = self.device_sampling(sampling)
        seeds, bases, temps, topks, topps, methods = sampling
        self._account(f"verify:{S}x{cache.bucket}x{toks.shape[1]}")
        with _tracing.child_span("model.verify", slots=S,
                                 bucket=cache.bucket):
            t = time.perf_counter()
            out_toks, new_ks, new_vs = self._verify_fn(
                self.params, cache._k, cache._v,
                jnp.asarray(toks),
                jnp.asarray(_np.asarray(positions, _np.int32)),
                seeds, bases, temps, topks, topps, methods)
            cache.replace(new_ks, new_vs)
            out = _np.asarray(out_toks)
            dt = time.perf_counter() - t
        _metrics.GEN_STEP_SECONDS.labels(phase="verify").observe(
            dt, exemplar=_tracing.current_trace_id())
        return out

    def prefill_suffix(self, tokens: _np.ndarray, prefix_ks: List[Any],
                       prefix_vs: List[Any], q: int, bucket_len: int
                       ) -> Tuple[_np.ndarray, List[Any], List[Any]]:
        """Run the prompt pass over only the SUFFIX ``tokens`` (real
        positions ``q..q+len``) against resident prefix K/V rows —
        the shared-prefix admission path.  Returns (last-real-token
        logits (V,) numpy, per-layer suffix ks/vs (bucket_len, nh,
        d)).  One compiled program per (prefix bucket, suffix bucket)
        pair; ``q`` and the real suffix length are traced operands."""
        import jax.numpy as jnp
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        t0 = toks.shape[0]
        if t0 < 1:
            raise MXNetError("empty prompt suffix")
        if bucket_len < t0:
            raise MXNetError(
                f"suffix length {t0} exceeds its bucket {bucket_len}")
        padded = _np.zeros((bucket_len,), _np.int32)
        padded[:t0] = toks
        Pb = int(prefix_ks[0].shape[0])
        self._account(f"prefill_sfx:{Pb}x{bucket_len}")
        with _tracing.child_span("model.prefill", bucket=bucket_len,
                                 family=self.family):
            t = time.perf_counter()
            logits, ks, vs = self._prefill_sfx_fn(
                self.params, list(prefix_ks), list(prefix_vs),
                jnp.asarray(padded), _np.int32(q), _np.int32(t0))
            out = _np.asarray(logits)
            dt = time.perf_counter() - t
        _metrics.GEN_STEP_SECONDS.labels(phase="prefill").observe(
            dt, exemplar=_tracing.current_trace_id())
        return out, ks, vs

    def warmup(self, cache: Any, prompt_buckets: Sequence[int],
               suffix_pairs: bool = True) -> int:
        """:meth:`DecodeModel.warmup`, and where ``suffix_pairs`` one
        suffix prefill per (prefix bucket, suffix bucket) pair (the
        shared-prefix admission path)."""
        import jax
        n = super().warmup(cache, prompt_buckets)
        if not suffix_pairs:
            return n
        dev = cache.device
        top = max(int(pb) for pb in prompt_buckets)
        rows = {int(pb): [jax.device_put(
            _np.zeros((int(pb), self.num_heads, self.head_dim),
                      self.dtype), dev)
            for _ in range(self.n_layers)]
            for pb in prompt_buckets}
        for Pb in prompt_buckets:
            for Sb in prompt_buckets:
                if int(Pb) + int(Sb) > top:
                    # unreachable at runtime: entries store
                    # bucket-aligned prefixes (Pb == q) and the
                    # admission capacity rule bounds q + Sb by the
                    # top prompt bucket — compiling these pairs
                    # would only inflate warmup and the persistent
                    # cache
                    continue
                self.prefill_suffix(
                    _np.zeros((1,), _np.int32), rows[int(Pb)],
                    rows[int(Pb)], q=1, bucket_len=int(Sb))
                n += 1
        return n
