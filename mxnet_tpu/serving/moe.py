"""MoEDecodeModel — the decode path of the Command A+ family
(``gluon.model_zoo.cohere2moe``): a parallel block whose expert branch
is one chip's share of an expert-parallel deployment.

What a slot holds, by layer kind (``PagedKVCache`` allocates it):

* ``window`` -> ``window``: K and V rows ``(S, kv, window)`` used as a
  ring, position ``p`` in column ``p % window``.  The window layers
  rotate q and k (RoPE at the absolute position) and the ring holds k
  ALREADY ROTATED: a score then depends on the two positions'
  difference alone, softmax does not care for the order of its keys,
  and the ring is never unrolled.
* ``full``   -> ``rows``: K and V rows ``(S, kv, L)`` in the bucket
  grid (no position embedding), written and grown as the GPT family's.

Prefill is one program a prompt bucket, built from the zoo's sequence
functions: a prompt is at most ``MAX_PROMPT`` positions, which the
window never cuts.  The decode step is one donated program a KV bucket
over every slot.  It reads EVERY cache by extent through the ragged
kernel (``ops.pallas.decode_attention``): the rows up to each slot's
position, the rings up to ``min(pos, window - 1)``; a ring read in
full would cost as much as the rows at the top bucket whatever the
position.  The expert branch routes every slot's token over all the
experts and computes the held experts' part (``parallel.moe``).

The step hands the held experts' LOAD back with the tokens, in one
int32 array ``(S + layers x held,)`` and so in one readback: the
choices that fell on each held expert of each layer, over every slot
the program ran (a free slot rides along at token 0 and is routed like
any other: the load is the grouped product's, not the requests').
``collect`` splits it, says it on the ``model.step.readback`` span and
moves the ``mxnet_gen_expert_*`` counters.  A prefill's load, over its
real tokens, goes on its ``model.prefill`` span: ``prefill`` reads it
at once, and a pass the engine dispatched (``dispatch_prefill``) has it
read by ``read_prefill`` with the admission's first token.

A ring cannot be rewound and a shared prefix would have to carry the
rings at its end: ``supports_rollback`` is False, so
``GenerationEngine`` refuses speculation and the prefix cache, and
``verify`` / ``prefill_suffix`` raise.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .. import metrics as _metrics
from .kv_cache import PagedKVCache
from .model import DecodeModel

__all__ = ["MoEDecodeModel"]

CACHE_KIND = {"window": "window", "full": "rows"}
# prompts are prefilled whole, one program a bucket; chunked prefill
# lifts it (ROADMAP), and a prompt past the window needs a windowed
# prefill kernel beside it
MAX_PROMPT = 1024
MIN_PROMPT_BUCKET = 64


class MoEDecodeModel(DecodeModel):
    """``DecodeModel`` for a ``Cohere2MoEModel``: the surface the engine
    drives (``prefill``, ``dispatch``, ``collect``, ``select``,
    ``warmup``)."""

    family = "cohere2moe"
    zoo = "mxnet_tpu.gluon.model_zoo.cohere2moe.Cohere2MoEModel"
    cache_buffers = 3
    max_prompt = MAX_PROMPT
    min_prompt_bucket = MIN_PROMPT_BUCKET
    supports_rollback = False
    no_rollback_why = (
        "it rewinds or shares a slot's rows, and this family's slots "
        "also hold window rings, which cannot be rewound and of which "
        "no snapshot is taken yet")

    def __init__(self, params: Any, cfg: Dict[str, Any], max_length: int,
                 name: str) -> None:
        super().__init__(params, cfg, max_length, name)
        lo, hi = cfg["experts_held"]
        self.held = hi - lo
        # the (layers, held) load of the step, and of the prefill, read
        # last: for a caller that drives the programs itself (a check)
        self.last_load: Optional[_np.ndarray] = None
        self.last_prefill_load: Optional[_np.ndarray] = None

    def _model_fns(self) -> Tuple[Any, Any]:
        import jax
        import jax.numpy as jnp
        from ..gluon.model_zoo import cohere2moe as _c2
        from ..ops.pallas import column_write as _cw
        from ..ops.pallas import decode_attention as _da
        cfg = self.cfg
        W = int(cfg["window"])
        kinds = self.kinds
        nkv, d = int(cfg["num_kv_heads"]), self.head_dim
        scale = 1.0 / math.sqrt(d)
        nth = [sum(k == kind for k in kinds[:i])
               for i, kind in enumerate(kinds)]

        def _prefill(params, toks, t0):
            # toks (Lp,) padded past the traced real length t0.  Returns
            # the last real token's logits, the full layers' rows as
            # write_prompt takes them, the window layers' rings at t0,
            # and the held experts' load over the real tokens
            from jax import lax
            Lp = toks.shape[0]
            hidden, rows, load = _c2.forward_sequence(params, toks, t0,
                                                      cfg)
            with jax.named_scope("head"):
                h = lax.dynamic_slice_in_dim(hidden, t0 - 1, 1, axis=0)[0]
            # ring column j holds the newest position < t0 that is
            # congruent to j; columns past t0 - 1 hold no position yet
            # and stay invisible until the step writes them
            j = jnp.arange(W)
            newest = jnp.clip(j + W * ((t0 - 1 - j) // W), 0, Lp - 1)
            ks, vs = [], []
            state: Dict[str, List[Any]] = {"wk": [], "wv": []}
            for kind, (k, v) in zip(kinds, rows):
                if kind == "window":
                    with jax.named_scope("cache/write"):
                        state["wk"].append(k.reshape(Lp, -1)[newest].T)
                        state["wv"].append(v.reshape(Lp, -1)[newest].T)
                else:
                    ks.append(k)
                    vs.append(v)
            return _c2.lm_logits(params, h, cfg), ks, vs, state, load

        def _logits(params, ks, vs, state, toks, pos):
            # the step's contract (model.DecodeModel._step): pos (S,),
            # free slots ride along at pos 0.  toks is the (S + layers x
            # held,) array this program returned last (or the host's
            # tokens padded to it): the S tokens lead.  ks/vs: the full
            # layers' rows; state: the window layers' rings.  The
            # layers' loads ride behind (:meth:`_step_output`)
            eps = cfg["layer_norm_eps"]
            S = pos.shape[0]
            with jax.named_scope("embed"):
                x = params["embed"][toks[:S]]
            ring = pos % W
            seen_ring = jnp.minimum(pos, W - 1)
            new = {name: list(bufs) for name, bufs in state.items()}
            ks, vs = list(ks), list(vs)
            loads = []
            for kind, i, p in zip(kinds, nth, params["layers"]):
                h = _c2._ln(x, p["ln_g"], eps)
                q, k, v = _c2.qkv(p, h, pos, kind, cfg)
                cols = (k.reshape(S, nkv * d), v.reshape(S, nkv * d))
                # the token's K and V column of every slot, one in-place
                # kernel call a layer: a ring's at pos % W, a row's at pos
                with jax.named_scope("cache/write"):
                    if kind == "window":
                        ck, cv = _cw.write_columns(
                            (new["wk"][i], new["wv"][i]), cols, ring)
                        new["wk"][i], new["wv"][i] = ck, cv
                        seen = seen_ring
                    else:
                        ck, cv = _cw.write_columns((ks[i], vs[i]), cols,
                                                   pos)
                        ks[i], vs[i] = ck, cv
                        seen = pos
                # query head n reads K/V head n // g: the kernel's
                # groups are the K/V heads, their g queries its rows
                with jax.named_scope("attn/core"):
                    a = _da.ragged_attention(q.reshape(S, nkv, -1, d), ck,
                                             cv, seen, scale)
                y, load = _c2.experts(p, h, cfg, grouped=False)
                loads.append(load)
                with jax.named_scope("attn/out"):
                    a = _c2._mm(a.reshape(S, -1).astype(h.dtype),
                                p["out_w"])
                x = x + (a + y).astype(x.dtype)
            with jax.named_scope("head"):
                x = _c2._ln(x, params["lnf_g"], eps)
                logits = _c2.lm_logits(params, x, cfg)
            return logits, ks, vs, new, loads

        return _prefill, _logits

    # -- what the engine asks of a family -----------------------------------
    def make_cache(self, max_slots: int, buckets: Sequence[int],
                   prefix_slots: Optional[int] = None,
                   prefix: Any = None) -> PagedKVCache:
        cfg = self.cfg
        return PagedKVCache(
            self.n_layers, cfg["num_kv_heads"], cfg["head_dim"],
            max_slots, buckets=buckets, dtype=self.dtype,
            prefix=prefix, prefix_slots=prefix_slots,
            kinds=[CACHE_KIND[k] for k in self.kinds],
            window=cfg["window"])

    def row_blocks(self, positions: _np.ndarray,
                   bucket: int) -> Tuple[int, int]:
        """The blocks of all the layers' caches: the rings' by
        ``min(pos, window - 1)`` of ``window``, the rows' by ``pos`` of
        ``bucket``."""
        from ..ops.pallas import decode_attention as _da
        pos, W = _np.asarray(positions), int(self.cfg["window"])
        n_ring = self.kinds.count("window")
        ring = _da.blocks_read(_np.minimum(pos, W - 1), W)
        rows = _da.blocks_read(pos, bucket)
        n_rows = self.n_layers - n_ring
        return (n_ring * ring[0] + n_rows * rows[0],
                n_ring * ring[1] + n_rows * rows[1])

    # -- execution: DecodeModel's prefill, dispatch and collect, with the
    # load taken out of what the programs hand back ---------------------
    def _prefill_extras(self, held: List[Any]) -> Tuple[List[Any], Any]:
        *held, load = held
        return held, load

    def _say_prefill(self, span: Any, load: Any) -> None:
        load = self.last_prefill_load = _np.asarray(load)
        span.set_attr(expert_assignments=int(load.sum()),
                      experts_hit=int((load > 0).sum()))

    def _step_output(self, tokens: Any, loads: List[Any]) -> Any:
        import jax.numpy as jnp
        return jnp.concatenate([tokens, jnp.stack(loads).reshape(-1)])

    def _step_tokens(self, tokens: _np.ndarray) -> _np.ndarray:
        out = _np.zeros((len(tokens) + self.n_layers * self.held,),
                        _np.int32)
        out[:len(tokens)] = tokens
        return out

    def _read_step(self, span: Any, out: _np.ndarray) -> _np.ndarray:
        S = out.shape[0] - self.n_layers * self.held
        load = self.last_load = out[S:].reshape(self.n_layers, self.held)
        assigned, hit = int(load.sum()), int((load > 0).sum())
        span.set_attr(expert_assignments=assigned, experts_hit=hit,
                      expert_load_max=int(load.max()),
                      expert_slots=int(load.size))
        _metrics.GEN_EXPERT_ASSIGNMENTS_TOTAL.inc(assigned)
        _metrics.GEN_EXPERT_OFFERED_TOTAL.inc(
            S * int(self.cfg["top_k"]) * self.n_layers)
        _metrics.GEN_EXPERTS_HIT_TOTAL.inc(hit)
        _metrics.GEN_EXPERT_SLOTS_TOTAL.inc(int(load.size))
        return out[:S]

    def describe(self) -> Dict[str, Any]:
        out = super().describe()
        cfg = self.cfg
        out.update(layer_kinds={k: self.kinds.count(k)
                                for k in dict.fromkeys(self.kinds)},
                   window=int(cfg["window"]), max_prompt=self.max_prompt,
                   kv_heads=int(cfg["num_kv_heads"]),
                   experts=int(cfg["num_experts"]),
                   experts_held=list(cfg["experts_held"]),
                   experts_per_token=int(cfg["top_k"]),
                   shared_experts=int(cfg["num_shared"]))
        return out
