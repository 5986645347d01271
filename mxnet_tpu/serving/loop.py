"""LoopDecodeModel — the decode path of the Ouro family
(``gluon.model_zoo.ouro``): one stack of layers run ``loop_steps`` times
a token with the same weights.

What a slot holds: ``rows`` only, but one entry a PASS.  Pass ``(t, l)``
(loop step ``t``, layer ``l``) attends the K and V that pass ``(t, l)``
of the earlier positions made, so the cache has ``loop_steps x layers``
entries, entry ``t * layers + l`` for pass ``(t, l)``, none shared.
They are STACKED: K is one array ``(entries, S, heads * d, L)`` and V
another (``PagedKVCache(stacked=True)``), because the programs index
them from inside a loop.

Both programs are LOOPS, not ``entries`` unrolled bodies: a
``fori_loop`` over the loop steps around a ``scan`` over the layers'
stacked weights.  The decode step carries K and V through both; pass
``(t, l)`` makes ONE kernel call on them
(``ops.pallas.decode_attention.append_and_attend``, the entry
scalar-prefetched): it walks the slots' live rows of the entry with the
token's column of every slot in place, and leaves that column written
in the stack, aliased to its operand, so no entry is ever sliced out of
the stack or copied and the tile that takes the column is fetched once.
Prefill is one program a prompt bucket from
the zoo's sequence function; its rows go into the slot through the
cache's donated admission write, all entries in one call.

At the published ``early_exit_threshold`` of 1 every token makes every
loop step and the head reads the last step's state; the exit gate's
probabilities are the zoo forward's to report, the programs here do not
compute them.  The span of a step says ``loop_steps`` and
``layer_passes``; ``mxnet_gen_loop_steps_total`` counts the decode
steps' loop steps.

Rows are all a slot holds, so a slot could be rewound and its prefix
shared; the suffix prefill and the verify program that would need are
not written yet: ``supports_rollback`` is False, ``GenerationEngine``
refuses speculation and the prefix cache, and ``verify`` /
``prefill_suffix`` raise.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as _np

from .. import metrics as _metrics
from .. import tracing as _tracing
from .kv_cache import PagedKVCache
from .model import DecodeModel, _sample_tokens, _select_one

__all__ = ["LoopDecodeModel"]

# prompts are prefilled whole, one program a bucket (chunked prefill
# lifts it, ROADMAP)
MAX_PROMPT = 1024
MIN_PROMPT_BUCKET = 64


def _entry(t, l, n_layers: int):
    """The cache entry of pass ``(t, l)``."""
    return t * n_layers + l


class LoopDecodeModel(DecodeModel):
    """``DecodeModel`` for an ``OuroModel``: the surface the engine
    drives (``prefill``, ``dispatch``, ``collect``, ``select``,
    ``warmup``)."""

    family = "loop"
    max_prompt = MAX_PROMPT
    min_prompt_bucket = MIN_PROMPT_BUCKET
    supports_rollback = False
    no_rollback_why = (
        "its slots hold rows alone and could be rewound or share a "
        "prefix, but the programs that would verify drafts or prefill a "
        "suffix over every loop step's entries are not written yet")

    def __init__(self, params: Any, cfg: Dict[str, Any], max_length: int,
                 name: str) -> None:
        # not DecodeModel.__init__: that builds the GPT programs
        import jax
        import jax.numpy as jnp
        from ..gluon.model_zoo import ouro as _ou
        from ..ops.pallas import decode_attention as _da
        self.params = params
        self.cfg = cfg
        self.max_length = int(max_length)
        self.name = name
        self.vocab_size, self.units = params["embed"].shape
        self.num_heads = int(cfg["num_heads"])
        self.head_dim = int(cfg["head_dim"])
        self.n_layers = int(cfg["num_layers"])
        self.loop_steps = int(cfg["loop_steps"])
        self.entries = self.loop_steps * self.n_layers
        self.span_attrs = {"loop_steps": self.loop_steps,
                           "layer_passes": self.entries}
        self.dtype = params["embed"].dtype
        self.logits_dtype = jnp.dtype(jnp.float32)
        self._seen_lock = threading.Lock()
        self._seen: set = set()
        steps, N = self.loop_steps, self.n_layers
        heads, d = self.num_heads, self.head_dim
        scale = 1.0 / math.sqrt(d)

        def _prefill(params, toks, t0):
            # toks (Lp,) padded past the traced real length t0 (causal:
            # the padding changes no row before it).  Returns the last
            # real token's logits and every entry's rows as
            # write_prompt takes stacked ones
            from jax import lax
            Lp = toks.shape[0]
            z, k, v = _ou.forward_sequence(params, toks, cfg)
            with jax.named_scope("head"):
                h = lax.dynamic_slice_in_dim(z[-1], t0 - 1, 1, axis=0)[0]
            return (_ou.lm_logits(params, h),
                    [k.reshape(steps * N, Lp, heads, d)],
                    [v.reshape(steps * N, Lp, heads, d)])

        def _step(params, ks, vs, toks, pos, seeds, bases, temps,
                  topks, topps, methods):
            # the GPT step's contract (model.DecodeModel._step): pos
            # (S,), free slots ride along at pos 0, the sampler in the
            # program.  ks/vs: the one stacked K and V
            from jax import lax
            S = pos.shape[0]

            def layer(t, carry, xs):
                x, K, V = carry
                p, l = xs
                e = _entry(t, l, N)
                q, k, v = _ou.qkv(p, x, pos, cfg)
                # each slot's live rows of entry e with the token's K
                # and V column in place, and the column left written
                with jax.named_scope("attn/core"):
                    a, K, V = _da.append_and_attend(
                        q.reshape(S, heads, 1, d), K, V,
                        k.reshape(S, heads * d), v.reshape(S, heads * d),
                        pos, scale, e)
                return (_ou.finish(p, x, a.reshape(S, heads * d), cfg),
                        K, V), None

            def loop_step(t, carry):
                (x, K, V), _ = lax.scan(
                    lambda c, xs: layer(t, c, xs), carry,
                    (params["layers"], jnp.arange(N)))
                return _ou.loop_output(params, x, t, cfg), K, V

            with jax.named_scope("embed"):
                x = params["embed"][toks]
            x, K, V = lax.fori_loop(0, steps, loop_step,
                                    (x, ks[0], vs[0]))
            logits = _ou.lm_logits(params, x)

            def _mixed(lg):
                return _sample_tokens(lg, seeds, pos - bases, temps,
                                      topks, topps, methods)

            def _greedy(lg):
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)

            with jax.named_scope("sample"):
                next_tok = lax.cond(jnp.any(methods != 0), _mixed,
                                    _greedy, logits)
            return next_tok, [K], [V]

        fam = self.family
        self._prefill_fn = _tracing.program(_prefill, "prefill", fam)
        self._select_fn = _tracing.program(_select_one, "select", fam)
        self._step_fn = _tracing.program(_step, "decode", fam,
                                         donate_argnums=(1, 2))

    @staticmethod
    def from_ouro(block: Any) -> "LoopDecodeModel":
        from ..gluon.model_zoo.ouro import _collect
        return LoopDecodeModel(_collect(block), dict(block.config),
                               block._max_length, type(block).__name__)

    # -- what the engine asks of a family -----------------------------------
    def make_cache(self, max_slots: int, buckets: Sequence[int],
                   prefix_slots: Optional[int] = None,
                   prefix: Any = None) -> PagedKVCache:
        return PagedKVCache(
            self.entries, self.num_heads, self.head_dim, max_slots,
            buckets=buckets, dtype=self.dtype, prefix=prefix,
            prefix_slots=prefix_slots, stacked=True)

    def row_blocks(self, positions: _np.ndarray,
                   bucket: int) -> Tuple[int, int]:
        """Every entry is read by the same extent, in the blocks of
        the appended walk."""
        from ..ops.pallas import decode_attention as _da
        read, every = _da.blocks_read(_np.asarray(positions), bucket,
                                      _da.append_block(bucket))
        return self.entries * read, self.entries * every

    def dispatch(self, cache: Any, tokens: Any, positions: _np.ndarray,
                 sampling: Optional[Sequence[Any]] = None) -> Any:
        _metrics.GEN_LOOP_STEPS_TOTAL.inc(self.loop_steps)
        return super().dispatch(cache, tokens, positions, sampling)

    def verify(self, *args: Any, **kwargs: Any) -> _np.ndarray:
        raise self.no_rollback("speculative verification")

    def prefill_suffix(self, *args: Any, **kwargs: Any) -> Any:
        raise self.no_rollback("suffix prefill over a shared prefix")

    def describe(self) -> Dict[str, Any]:
        out = super().describe()
        out.update(loop_steps=self.loop_steps, layer_passes=self.entries,
                   exit_threshold=float(self.cfg["exit_threshold"]),
                   max_prompt=self.max_prompt)
        return out
