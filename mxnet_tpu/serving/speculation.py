"""Speculative decoding: draft models that propose k tokens per slot
for one-pass target verification.

The GenerationEngine's plain decode loop emits exactly one token per
slot per iteration, so tokens/sec is hard-capped by target-model step
latency (the tokens/sec/chip economics PAPERS.md frames).  Speculation
breaks the cap: a cheap DRAFT proposes ``k`` tokens per slot, the
target scores all ``k+1`` positions in ONE bucket-compiled pass
(:meth:`DecodeModel.verify`), and the engine keeps the longest prefix
of proposals that MATCH the target's own tokens — every emitted token
is the target's, so output is byte-identical to the non-speculative
engine at the same seed (CI pins this for greedy and sampled traffic).

**Accept rule.**  Draft proposal ``d_j`` (for stream position ``p+j``)
is accepted iff it equals the token the target itself produces at that
position — greedy argmax, or the PR-12 counter-PRNG sample under the
slot's folded key ``fold_in(PRNGKey(seed), position - base)``.  Both
drafts below therefore run the SAME per-slot sampling lanes as the
target: a good draft reproduces the target's categorical draw exactly
(identical logits => identical token under an identical key), so
sampled traffic speculates as well as greedy.  Greedy is just the
``method=0`` special case where the key never matters.

**Rollback.**  Verification scatters K/V rows for all ``k+1``
positions; when only ``m <= k`` tokens survive, the engine rewinds the
slot with :meth:`PagedKVCache.truncate` — pure host bookkeeping (the
rows were never visible past the slot position) counted in
``mxnet_gen_kv_rollbacks_total``.

Two draft flavors:

* :class:`SelfSpeculativeDraft` — the target's own bottom ``n`` layers
  (plus its final norm and tied head) act as the draft.  Zero extra
  parameters, zero extra KV state: the chained draft steps READ the
  target cache's first ``n`` layer buffers (never donated, temporaries
  discarded), so rollback only ever concerns the verify pass's writes.
* :class:`IndependentDraft` — a separate small zoo GPT sharing the
  target's tokenizer, with its own :class:`PagedKVCache` mirroring the
  target's slot ids.  Each iteration runs ``k+1`` chained sub-steps
  (the extra one writes the row for the last proposal), so after
  truncating to the accepted boundary the draft cache position always
  equals the target's — no catch-up pass exists anywhere.
"""
from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError
from .. import metrics as _metrics
from .. import tracing as _tracing
from .kv_cache import PagedKVCache, round_up_bucket
from .model import DecodeModel, _pure_ln, _sample_tokens, \
    _slot_block_step

__all__ = ["DraftModel", "SelfSpeculativeDraft", "IndependentDraft",
           "make_draft"]


def _chain_steps(params, ks, vs, toks, pos, seeds, bases, temps,
                 topks, topps, methods, n_sub, nh, ga_s):
    """``n_sub`` UNROLLED single-token steps feeding each output token
    back in as the next input — the draft-proposal chain.  Sub-step
    ``j`` scatters K/V at ``pos + j`` and samples under counter
    ``pos + j - base``: exactly the key the target's verify pass uses
    for that position, so a draft whose logits match the target's
    proposes the target's own token (the accept rule's fixed point)."""
    import jax
    from jax import lax
    import jax.numpy as jnp
    cur = toks
    outs = []
    for j in range(n_sub):
        with jax.named_scope("embed"):
            x = (params["embed"][cur][:, None, :]
                 + params["pos"][pos + j][:, None, :])
        new_ks, new_vs = [], []
        for p, ck, cv in zip(params["blocks"], ks, vs):
            x, ck, cv = _slot_block_step(p, x, ck, cv, pos + j,
                                         nh, ga_s)
            new_ks.append(ck)
            new_vs.append(cv)
        ks, vs = new_ks, new_vs
        with jax.named_scope("head"):
            x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga_s[1])
            logits = x[:, 0, :] @ params["embed"].T

        def _mixed(lg, _j=j):
            return _sample_tokens(lg, seeds, (pos + _j) - bases,
                                  temps, topks, topps, methods)

        def _greedy(lg):
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        with jax.named_scope("sample"):
            cur = lax.cond(jnp.any(methods != 0), _mixed, _greedy,
                           logits)
        outs.append(cur)
    return jnp.stack(outs, axis=1), ks, vs


class DraftModel:
    """The engine-facing draft protocol.

    A draft owns whatever state its proposals need; the engine drives
    it with slot-parallel calls mirroring its own lifecycle:

    * :meth:`admit` / :meth:`release` bracket a speculative request's
      residency in ``slot``.
    * :meth:`propose` returns an ``(S, k)`` int32 proposal matrix for
      every slot (garbage rows for non-speculative slots are fine —
      the engine discards them).
    * :meth:`commit` tells the draft the slot's post-acceptance
      position so cache-bearing drafts can truncate their own rows.
    * :meth:`evacuate` / :meth:`reset` / :meth:`reset_if_empty` mirror
      the engine's failure/idle paths; :meth:`warmup` pre-compiles the
      draft's programs so steady-state traffic stays at zero compiles.
    """

    mode = "?"
    k = 0

    def admit(self, slot: int, tokens: _np.ndarray,
              prompt_buckets: Sequence[int]) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def commit(self, slot: int, position: int) -> None:
        pass

    def evacuate(self) -> None:
        pass

    def reset(self) -> None:
        pass

    def reset_if_empty(self) -> None:
        pass

    def warmup(self, prompt_buckets: Sequence[int]) -> int:
        return 0

    def propose(self, cache: Any, last_tok: _np.ndarray,
                positions: _np.ndarray,
                sampling: Optional[Sequence[Any]] = None) -> _np.ndarray:
        raise NotImplementedError

    def describe(self) -> dict:
        return {"mode": self.mode, "k": self.k}


class SelfSpeculativeDraft(DraftModel):
    """Truncated-layer self-speculation: the target's bottom ``layers``
    blocks + final norm + tied head propose the next ``k`` tokens.

    The chained draft steps read the TARGET cache's first ``layers``
    K/V buffers in place (not donated — XLA materializes the chain's
    scatters into temporaries that die with the call), so the draft
    adds no resident state and the engine's rollback story stays
    entirely about the verify pass's writes."""

    mode = "self"

    def __init__(self, model: Any, k: int, layers: int = 0) -> None:
        import jax
        if not isinstance(model, DecodeModel):
            model = DecodeModel.from_block(model)
        self.model = model
        self.k = int(k)
        if self.k < 1:
            raise MXNetError(f"speculative k must be >= 1, got {k}")
        layers = int(layers)
        if layers == 0:
            layers = max(1, model.n_layers // 2)
        if not 1 <= layers <= model.n_layers:
            raise MXNetError(
                f"self-speculative draft wants 1..{model.n_layers} "
                f"target layers, got {layers}")
        self.layers = layers
        nh, ga_s = model.num_heads, model.ga
        n_sub = self.k

        def _propose(params, ks, vs, toks, pos, seeds, bases, temps,
                     topks, topps, methods):
            outs, _, _ = _chain_steps(params, ks, vs, toks, pos,
                                      seeds, bases, temps, topks,
                                      topps, methods, n_sub, nh, ga_s)
            return outs

        self._fn = _tracing.program(_propose, "draft", model.family,
                                    attrs={"mode": self.mode})

    def _sub_params(self) -> dict:
        p = self.model.params
        return {"embed": p["embed"], "pos": p["pos"],
                "lnf_g": p["lnf_g"], "lnf_b": p["lnf_b"],
                "blocks": list(p["blocks"][:self.layers])}

    def propose(self, cache: Any, last_tok: _np.ndarray,
                positions: _np.ndarray,
                sampling: Optional[Sequence[Any]] = None) -> _np.ndarray:
        import jax
        import jax.numpy as jnp
        S = cache.max_slots
        if sampling is None:
            sampling = self.model.greedy_sampling(S)
        if not isinstance(sampling[0], jax.Array):
            sampling = self.model.device_sampling(sampling)
        self.model._account(f"draft:{S}x{cache.bucket}x{self.k}")
        t = time.perf_counter()
        outs = self._fn(
            self._sub_params(),
            list(cache._k[:self.layers]), list(cache._v[:self.layers]),
            jnp.asarray(_np.asarray(last_tok, _np.int32)),
            jnp.asarray(_np.asarray(positions, _np.int32)), *sampling)
        out = _np.asarray(outs)
        _metrics.GEN_STEP_SECONDS.labels(phase="draft").observe(
            time.perf_counter() - t,
            exemplar=_tracing.current_trace_id())
        return out

    def describe(self) -> dict:
        return {"mode": self.mode, "k": self.k, "layers": self.layers,
                "target_layers": self.model.n_layers}


class IndependentDraft(DraftModel):
    """A separate small GPT drafting against its own
    :class:`PagedKVCache` whose slot ids mirror the target's.

    The deficit-zero invariant: every :meth:`propose` runs ``k+1``
    chained sub-steps — the extra step exists purely to write the K/V
    row for the last proposal — so the draft cache always holds rows
    for exactly the positions the target holds once :meth:`commit`
    truncates both to the accepted boundary.  Admission prefills the
    draft cache from the same prompt (same tokenizer — the factory
    enforces matching vocab)."""

    mode = "draft"

    def __init__(self, model: Any, k: int, max_slots: int,
                 buckets: Optional[Sequence[int]] = None) -> None:
        import jax
        if not isinstance(model, DecodeModel):
            model = DecodeModel.from_block(model)
        self.model = model
        self.k = int(k)
        if self.k < 1:
            raise MXNetError(f"speculative k must be >= 1, got {k}")
        self.cache = PagedKVCache(
            model.n_layers, model.num_heads, model.head_dim,
            int(max_slots), buckets=buckets, dtype=model.dtype,
            prefix_slots=0)
        if self.cache.grid[-1] > model.max_length:
            raise MXNetError(
                f"draft model context {model.max_length} is shorter "
                f"than the KV bucket grid top {self.cache.grid[-1]} — "
                "the draft could not follow a full-length sequence")
        nh, ga_s = model.num_heads, model.ga
        n_sub = self.k + 1

        def _propose(params, ks, vs, toks, pos, seeds, bases, temps,
                     topks, topps, methods):
            outs, ks, vs = _chain_steps(params, ks, vs, toks, pos,
                                        seeds, bases, temps, topks,
                                        topps, methods, n_sub, nh,
                                        ga_s)
            return outs, ks, vs

        # the draft cache's buffers are donated exactly like the
        # target step's: the chain updates them in place
        self._fn = _tracing.program(_propose, "draft", model.family,
                                    attrs={"mode": self.mode},
                                    donate_argnums=(1, 2))

    def admit(self, slot: int, tokens: _np.ndarray,
              prompt_buckets: Sequence[int]) -> None:
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        t0 = toks.shape[0]
        pb = round_up_bucket(t0, prompt_buckets)
        _, ks, vs = self.model.prefill(toks, pb)
        self.cache.write_prompt(slot, ks, vs, t0)

    def release(self, slot: int) -> None:
        self.cache.free(slot)

    def commit(self, slot: int, position: int) -> None:
        if self.cache.positions[slot] < 0:
            return
        dp = int(self.cache.positions[slot])
        # propose wrote rows dp..dp+k; adopt them, then rewind to the
        # target's accepted boundary (== dp+k+1 on full acceptance)
        self.cache.positions[slot] = dp + self.k + 1
        if position < dp + self.k + 1:
            self.cache.truncate(slot, position)

    def evacuate(self) -> None:
        self.cache.positions.fill(-1)
        self.cache.reset_buffers()

    def reset(self) -> None:
        self.cache.reset_buffers()

    def reset_if_empty(self) -> None:
        self.cache.reset_if_empty()

    def warmup(self, prompt_buckets: Sequence[int]) -> int:
        n = 0
        one = _np.zeros((1,), _np.int32)
        for pb in prompt_buckets:
            if pb > self.model.max_length:
                continue
            self.model.prefill(one, int(pb))
            n += 1
        n += self.cache.warmup_writes(prompt_buckets)
        S = self.cache.max_slots
        toks = _np.zeros((S,), _np.int32)
        for b in self.cache.grid:
            self.cache.bucket = int(b)
            self.cache._alloc_buffers(self.cache.bucket)
            self.propose(None, toks, None)
            n += 1
        self.cache.bucket = self.cache.grid[0]
        self.cache._alloc_buffers(self.cache.bucket)
        return n

    def propose(self, cache: Any, last_tok: _np.ndarray,
                positions: _np.ndarray = None,
                sampling: Optional[Sequence[Any]] = None) -> _np.ndarray:
        # ``cache``/``positions`` are the TARGET's — the draft follows
        # its own mirror (equal for every speculative slot by the
        # deficit-zero invariant; free/non-speculative slots ride at 0
        # and their proposals are discarded)
        import jax
        import jax.numpy as jnp
        S = self.cache.max_slots
        if sampling is None:
            sampling = self.model.greedy_sampling(S)
        if not isinstance(sampling[0], jax.Array):
            sampling = self.model.device_sampling(sampling)
        self.cache.ensure_capacity(
            min(self.cache.needed_capacity() + self.k,
                self.cache.grid[-1]))
        pos = _np.maximum(self.cache.positions, 0).astype(_np.int32)
        self.model._account(
            f"draft:{S}x{self.cache.bucket}x{self.k}")
        t = time.perf_counter()
        outs, new_ks, new_vs = self._fn(
            self.model.params, self.cache._k, self.cache._v,
            jnp.asarray(_np.asarray(last_tok, _np.int32)),
            jnp.asarray(pos), *sampling)
        self.cache.replace(new_ks, new_vs)
        out = _np.asarray(outs)[:, :self.k]
        _metrics.GEN_STEP_SECONDS.labels(phase="draft").observe(
            time.perf_counter() - t,
            exemplar=_tracing.current_trace_id())
        return out

    def describe(self) -> dict:
        return {"mode": self.mode, "k": self.k,
                "draft_model": self.model.describe(),
                "draft_cache": self.cache.describe()}


def make_draft(mode: Optional[str], target: DecodeModel, k: int,
               layers: int = 0, draft_model: Any = None,
               max_slots: int = 0,
               buckets: Optional[Sequence[int]] = None
               ) -> Optional[DraftModel]:
    """Build the draft the engine's spec config asks for (None when
    ``mode`` is off/empty)."""
    if mode in (None, "", "off"):
        return None
    if mode == "self":
        return SelfSpeculativeDraft(target, k, layers)
    if mode == "draft":
        if draft_model is None:
            raise MXNetError(
                "speculative mode 'draft' needs a draft model "
                "(pass draft_model= to the engine; MXNET_GEN_SPEC_MODE "
                "alone cannot conjure one)")
        d = IndependentDraft(draft_model, k, max_slots, buckets=buckets)
        if d.model.vocab_size != target.vocab_size:
            raise MXNetError(
                f"draft vocab {d.model.vocab_size} != target vocab "
                f"{target.vocab_size} — speculation requires a shared "
                "tokenizer")
        return d
    raise MXNetError(
        f"unknown speculative mode {mode!r} (want off|self|draft)")
