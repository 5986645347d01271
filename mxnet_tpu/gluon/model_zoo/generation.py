"""Autoregressive text generation for the GPT family — KV-cache
incremental decoding, TPU-native.

Reference-ecosystem parity: gluon-nlp's ``SequenceSampler`` /
``BeamSearchSampler`` were the inference story beside BERT (the
reference's own repo had no decoder-only LM). Here decoding is designed
for XLA from the start:

* **Static shapes everywhere** — the KV cache is a fixed
  ``(B, max_len, heads, d)`` buffer written with
  ``lax.dynamic_update_slice_in_dim``; attention over the cache masks
  positions ``> pos`` instead of slicing a dynamic length.
* **One compiled program per decode** — prefill + a ``lax.scan`` over
  decode steps compile once per (batch, prompt-length, new-tokens,
  method) signature and are cached.
* **Sampling on-device** — greedy / temperature / top-k draw from the
  threefry PRNG inside the scan; beam search reorders the cache with
  batched gathers.

The pure-jax block math mirrors ``GPTBlock.forward`` exactly (same LN /
GELU / scale conventions); the equivalence is pinned by
``tests/test_gpt.py`` (cached decode logits == full forward logits).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...base import MXNetError

__all__ = ["generate", "beam_search"]


# ---------------------------------------------------------------------------
# parameter extraction (block objects -> pure pytrees)
# ---------------------------------------------------------------------------

def _j(p) -> jnp.ndarray:
    return jnp.asarray(p.data()._data)


def _collect(model) -> Dict[str, Any]:
    blocks: List[Dict[str, jnp.ndarray]] = []
    for blk in model.blocks._children.values():
        if blk.moe is not None:
            raise MXNetError(
                "generate() does not support MoE blocks of the GPT "
                "family: MoEDense routes against a capacity mask that "
                "drops tokens, and no decode step is built over it. "
                "The dropless expert layer (parallel.moe.route / "
                "expert_product) is decoded for the cohere2moe family "
                "(serving.moe)")
        blocks.append({
            "ln1_g": _j(blk.ln1.gamma), "ln1_b": _j(blk.ln1.beta),
            "qkv_w": _j(blk.attn_qkv.weight),
            "qkv_b": _j(blk.attn_qkv.bias),
            "out_w": _j(blk.attn_out.weight),
            "out_b": _j(blk.attn_out.bias),
            "ln2_g": _j(blk.ln2.gamma), "ln2_b": _j(blk.ln2.beta),
            "f1_w": _j(blk.ffn1.weight), "f1_b": _j(blk.ffn1.bias),
            "f2_w": _j(blk.ffn2.weight), "f2_b": _j(blk.ffn2.bias),
        })
    approx = any(blk._gelu_approximate
                 for blk in model.blocks._children.values())
    eps = float(next(iter(
        model.blocks._children.values())).ln1._epsilon)
    return {
        "gelu_approx": approx,
        "ln_eps": eps,
        "embed": _j(model.word_embed.weight),
        "pos": _j(model.position_weight),
        "lnf_g": _j(model.ln_f.gamma), "lnf_b": _j(model.ln_f.beta),
        "blocks": blocks,
    }


# ---------------------------------------------------------------------------
# pure block math (must mirror GPTBlock.forward / ops.nn exactly)
# ---------------------------------------------------------------------------

def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _block_prefill(p, x, nh: int, L: int, ga=(False, 1e-5)):
    gelu_approx, eps = ga
    """Full causal pass over the prompt; returns (x_out, ck, cv) with
    the caches zero-padded to length L."""
    B, T, C = x.shape
    d = C // nh
    with jax.named_scope("attn/qkv"):
        h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        qh = q.reshape(B, T, nh, d)
        kh = k.reshape(B, T, nh, d)
        vh = v.reshape(B, T, nh, d)
    with jax.named_scope("attn/core"):
        out = jax.nn.dot_product_attention(qh, kh, vh, is_causal=True)
    with jax.named_scope("attn/out"):
        x = x + (out.reshape(B, T, C) @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        x = x + (ffn @ p["f2_w"].T + p["f2_b"])
    pad = [(0, 0), (0, L - T), (0, 0), (0, 0)]
    return x, jnp.pad(kh, pad), jnp.pad(vh, pad)


def _block_step(p, x, ck, cv, pos, nh: int, ga=(False, 1e-5)):
    gelu_approx, eps = ga
    """One-token decode: x (B, 1, C), caches (B, L, nh, d), pos scalar.
    Writes position ``pos`` then attends over cache[0..pos]."""
    B, _, C = x.shape
    d = C // nh
    L = ck.shape[1]
    with jax.named_scope("attn/qkv"):
        h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        qh = q.reshape(B, 1, nh, d)
    with jax.named_scope("cache/write"):
        ck = lax.dynamic_update_slice_in_dim(ck, k.reshape(B, 1, nh, d),
                                             pos, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cv, v.reshape(B, 1, nh, d),
                                             pos, axis=1)
    with jax.named_scope("attn/core"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, ck) / math.sqrt(d)
        visible = jnp.arange(L) <= pos              # static-shape mask
        scores = jnp.where(visible[None, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, cv).reshape(B, 1, C)
    with jax.named_scope("attn/out"):
        x = x + (out @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        x = x + (ffn @ p["f2_w"].T + p["f2_b"])
    return x, ck, cv


def _embed_one(params, tok, pos):
    """(B,) token ids at scalar position pos -> (B, 1, C)."""
    x = params["embed"][tok][:, None, :]
    return x + lax.dynamic_slice_in_dim(params["pos"], pos, 1,
                                        axis=0)[None, :, :]


def _forward_step(params, tok, caches, pos, nh, ga=(False, 1e-5)):
    """Embed one token, run all blocks against the caches, return
    (logits (B, V), new caches)."""
    x = _embed_one(params, tok, pos)
    new_caches = []
    for p, (ck, cv) in zip(params["blocks"], caches):
        x, ck, cv = _block_step(p, x, ck, cv, pos, nh, ga)
        new_caches.append((ck, cv))
    x = _ln(x, params["lnf_g"], params["lnf_b"], ga[1])
    return x[:, 0, :] @ params["embed"].T, new_caches


def _prefill(params, tokens, nh, L, ga=(False, 1e-5)):
    x = params["embed"][tokens] + params["pos"][None, :tokens.shape[1]]
    caches = []
    for p in params["blocks"]:
        x, ck, cv = _block_prefill(p, x, nh, L, ga)
        caches.append((ck, cv))
    x = _ln(x, params["lnf_g"], params["lnf_b"], ga[1])
    return x[:, -1, :] @ params["embed"].T, caches


def _select(logits, method, temperature, top_k, top_p, key):
    if method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if method == "top_k":
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    elif method == "top_p":
        # nucleus sampling: keep the smallest prefix of the
        # probability-sorted vocab whose cumulative mass reaches top_p
        # (the most probable token is always kept)
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p
        kth = jnp.min(jnp.where(keep, sorted_desc, jnp.inf),
                      axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    elif method != "sample":
        raise MXNetError(f"unknown generation method {method!r}")
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class _LRU(dict):
    """Bounded program cache: compiled decode executables are big, and a
    serving loop over varying prompt lengths must not pin one per shape
    forever."""

    MAX = 32

    def get(self, key, default=None):
        v = super().get(key, default)
        if key in self:                     # refresh recency
            super().__delitem__(key)
            super().__setitem__(key, v)
        return v

    def __setitem__(self, key, value):
        if key in self:
            super().__delitem__(key)
        elif len(self) >= self.MAX:
            super().__delitem__(next(iter(self)))
        super().__setitem__(key, value)


_PROG_CACHE: Dict[Any, Any] = _LRU()


def _prepare(model, tokens, max_new_tokens: int):
    """Shared generate/beam prolog: coerce tokens, validate lengths,
    collect params. Returns (toks (B,T0) int32 numpy, params, nh, L)."""
    import numpy as onp
    toks = onp.asarray(tokens.asnumpy() if hasattr(tokens, "asnumpy")
                       else tokens, dtype="int32")
    if toks.ndim == 1:
        toks = toks[None, :]
    if max_new_tokens < 1:
        raise MXNetError("max_new_tokens must be >= 1")
    L = toks.shape[1] + max_new_tokens
    if L > model._max_length:
        raise MXNetError(
            f"prompt ({toks.shape[1]}) + new tokens ({max_new_tokens}) "
            f"exceeds max_length {model._max_length}")
    nh = next(iter(model.blocks._children.values()))._num_heads
    params = _collect(model)
    # static compile-time config — must NOT ride the jitted pytree
    ga = (params.pop("gelu_approx"), params.pop("ln_eps"))
    return toks, params, nh, L, ga


def _model_sig(params, nh, ga):
    """Structural cache key — NOT id(model): a reused address must not
    serve a stale program, and identical-architecture models can share
    one compiled decode."""
    V, C = params["embed"].shape
    return (nh, V, C, params["pos"].shape[0], len(params["blocks"]), ga)


def generate(model, tokens, max_new_tokens: int, method: str = "greedy",
             temperature: float = 1.0, top_k: int = 40,
             eos_token: Optional[int] = None, seed: int = 0,
             top_p: float = 0.9):
    """Decode ``max_new_tokens`` continuations of ``tokens`` (B, T0).

    ``method``: 'greedy', 'sample', 'top_k', or 'top_p' (nucleus —
    sample from the smallest probability-sorted vocab prefix whose
    cumulative mass reaches ``top_p``). Returns an int32 array
    (B, max_new_tokens). After ``eos_token`` (if given) a sequence keeps
    emitting ``eos_token``. One XLA program per (shape, method)
    signature — repeated calls reuse the compiled prefill+scan.
    """
    import numpy as onp
    toks, params, nh, L, ga = _prepare(model, tokens, max_new_tokens)
    B, T0 = toks.shape
    eos = -1 if eos_token is None else int(eos_token)
    if method == "top_k":
        V = params["embed"].shape[0]
        if not 1 <= top_k:
            raise MXNetError(f"top_k must be >= 1, got {top_k}")
        top_k = min(int(top_k), V)
    if method == "top_p" and not 0.0 < top_p <= 1.0:
        raise MXNetError(f"top_p must be in (0, 1], got {top_p}")

    sig = ("gen", _model_sig(params, nh, ga), B, T0, max_new_tokens,
           method, float(temperature), int(top_k), float(top_p), eos)
    prog = _PROG_CACHE.get(sig)
    if prog is None:
        def run(params, toks, key):
            logits, caches = _prefill(params, toks, nh, L, ga)
            key, sub = jax.random.split(key)
            first = _select(logits, method, temperature, top_k, top_p,
                            sub)
            if eos >= 0:
                done0 = first == eos
            else:
                done0 = jnp.zeros((B,), bool)

            def step(carry, i):
                caches, tok, done, key = carry
                pos = T0 + i
                logits, caches = _forward_step(params, tok, caches,
                                               pos, nh, ga)
                key, sub = jax.random.split(key)
                nxt = _select(logits, method, temperature, top_k, top_p,
                              sub)
                if eos >= 0:
                    nxt = jnp.where(done, eos, nxt)
                    done = done | (nxt == eos)
                return (caches, nxt, done, key), nxt

            if max_new_tokens == 1:
                return first[:, None]
            (_, _, _, _), rest = lax.scan(
                step, (caches, first, done0, key),
                jnp.arange(max_new_tokens - 1))
            return jnp.concatenate([first[:, None], rest.T], axis=1)

        prog = jax.jit(run)
        _PROG_CACHE[sig] = prog
    out = prog(params, jnp.asarray(toks),
               jax.random.PRNGKey(seed))
    from ...ndarray.ops import array
    return array(onp.asarray(out))


def beam_search(model, tokens, max_new_tokens: int, beam_size: int = 4,
                eos_token: Optional[int] = None, alpha: float = 1.0):
    """Length-normalized beam search (gluon-nlp ``BeamSearchSampler``
    analog: scores = logprob_sum / length^alpha).

    ``tokens`` (B, T0) -> (sequences (B, beam, max_new_tokens), scores
    (B, beam)), beams sorted best-first. The KV caches expand to
    B*beam rows once and are reordered per step with batched gathers —
    no re-prefill, static shapes throughout.
    """
    import numpy as onp
    toks, params, nh, L, ga = _prepare(model, tokens, max_new_tokens)
    B, T0 = toks.shape
    K = int(beam_size)
    if K < 1:
        raise MXNetError(f"beam_size must be >= 1, got {K}")
    eos = -1 if eos_token is None else int(eos_token)
    NEG = jnp.float32(-1e30)

    sig = ("beam", _model_sig(params, nh, ga), B, T0, max_new_tokens,
           K, eos, float(alpha))
    prog = _PROG_CACHE.get(sig)
    if prog is None:
        def run(params, toks):
            logits, caches = _prefill(params, toks, nh, L, ga)  # (B, V)
            V = logits.shape[-1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            # seed the beams from the prompt's top-K continuations
            scores, first = lax.top_k(logp, K)               # (B, K)
            # expand caches to B*K rows (beam-major within batch)
            caches = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, K, axis=0), caches)
            tok = first.reshape(B * K)
            done = (tok == eos) if eos >= 0 else jnp.zeros((B * K,), bool)
            seqs0 = jnp.zeros((B, K, max_new_tokens), jnp.int32)
            seqs0 = seqs0.at[:, :, 0].set(first)

            def step(carry, i):
                caches, tok, scores, seqs, done = carry
                pos = T0 + i
                logits, caches = _forward_step(params, tok, caches,
                                               pos, nh, ga)   # (B*K, V)
                logp = jax.nn.log_softmax(logits, axis=-1)
                logp = logp.reshape(B, K, V)
                if eos >= 0:
                    # a finished beam only extends with eos at no cost
                    only_eos = jnp.full((V,), NEG).at[eos].set(0.0)
                    logp = jnp.where(done.reshape(B, K, 1), only_eos,
                                     logp)
                cand = scores[:, :, None] + logp              # (B, K, V)
                flat = cand.reshape(B, K * V)
                scores, idx = lax.top_k(flat, K)              # (B, K)
                beam_src = idx // V                           # (B, K)
                tok = (idx % V).astype(jnp.int32)
                # reorder beam state: rows are beam-major per batch
                gather = (jnp.arange(B)[:, None] * K
                          + beam_src).reshape(B * K)
                caches = jax.tree_util.tree_map(
                    lambda c: c[gather], caches)
                seqs = jnp.take_along_axis(
                    seqs, beam_src[:, :, None], axis=1)
                seqs = seqs.at[:, :, i + 1].set(tok)
                done = done[gather]
                tokf = tok.reshape(B * K)
                if eos >= 0:
                    done = done | (tokf == eos)
                return (caches, tokf, scores, seqs, done), None

            if max_new_tokens > 1:
                (caches, tok, scores, seqs, done), _ = lax.scan(
                    step, (caches, tok, scores, seqs0, done),
                    jnp.arange(max_new_tokens - 1))
            else:
                seqs = seqs0
            # length-normalized final ranking (finished beams measure
            # their true length up to eos)
            if eos >= 0:
                lengths = jnp.sum(
                    jnp.cumsum(seqs == eos, axis=-1) == 0, axis=-1) + 1
                lengths = jnp.minimum(lengths, max_new_tokens)
            else:
                lengths = jnp.full((B, K), max_new_tokens)
            norm = scores / (lengths.astype(jnp.float32) ** alpha)
            order = jnp.argsort(-norm, axis=-1)
            seqs = jnp.take_along_axis(seqs, order[:, :, None], axis=1)
            norm = jnp.take_along_axis(norm, order, axis=1)
            return seqs, norm

        prog = jax.jit(run)
        _PROG_CACHE[sig] = prog
    seqs, scores = prog(params, jnp.asarray(toks))
    from ...ndarray.ops import array
    return array(onp.asarray(seqs)), array(onp.asarray(scores))
