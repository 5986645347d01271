"""DeltaDecodeModel — the decode path of the Olmo Hybrid family
(``gluon.model_zoo.olmo_hybrid``): Gated DeltaNet layers beside full
multi-head attention.

What a slot holds, by layer kind (``PagedKVCache`` allocates it):

* ``linear`` -> ``state``: the conv's last ``conv_size - 1`` inputs
  ``(S, conv_size - 1, conv)`` and the delta rule's matrix state
  ``(S, key_dim, heads * value_dim)`` (a head's ``(key_dim,
  value_dim)`` side by side along the lanes:
  ``ops.pallas.delta_step.to_lanes``), float32, the same size at every
  position.
* ``full``   -> ``rows``: K and V rows ``(S, heads * d, L)`` in the
  bucket grid, written and grown as the other families'.

Prefill is one program a prompt bucket, built from the zoo's sequence
functions: the delta rule over the prompt in chunks
(``olmo_hybrid.delta_chunked``), the full layers through the flash
kernel from 512 positions on.  It hands back both kinds AT THE PROMPT'S
REAL LENGTH: the recurrence stops there (``beta`` and the log decay are
zeroed past it), the conv's state is gathered from there.  The decode
step is one donated program a KV bucket over every slot.  It moves
every slot's state of a linear layer one token on with ONE kernel call
(``ops.pallas.delta_step``, the state read once and written once, in
place), writes the token's K/V column of every slot with one kernel
call a full layer (``ops.pallas.column_write``) and reads the rows by
extent (``ops.pallas.decode_attention.ragged_attention``).  Matrices
and activations are the block's dtype (bfloat16 as published); the
recurrence, its state, RMSNorm, softmax and the logits are float32.

Rolling a slot back (speculation) or sharing a prefix needs snapshots
of the recurrent state, which no one takes yet: ``GenerationEngine``
refuses both for this family, and ``verify`` / ``prefill_suffix``
raise.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .kv_cache import PagedKVCache
from .model import DecodeModel

__all__ = ["DeltaDecodeModel"]

CACHE_KIND = {"linear": "state", "full": "rows"}
# prompts are prefilled whole, one program a bucket: the full layers'
# (heads, T, T) scores below 512 positions bound it (the delta rule's
# chunks are products, their inverses block merges, linear in T); a
# chunked prefill over the cache would lift it (ROADMAP)
MAX_PROMPT = 1024
# the delta rule's chunk
MIN_PROMPT_BUCKET = 64


class DeltaDecodeModel(DecodeModel):
    """``DecodeModel`` for an ``OlmoHybridModel``: the surface the
    engine drives (``prefill``, ``dispatch``, ``collect``, ``select``,
    ``warmup``), the per-slot state carried as one pytree of the
    cache's buffers."""

    family = "olmo_hybrid"
    zoo = "mxnet_tpu.gluon.model_zoo.olmo_hybrid.OlmoHybridModel"
    cache_buffers = 3
    max_prompt = MAX_PROMPT
    min_prompt_bucket = MIN_PROMPT_BUCKET
    supports_rollback = False
    no_rollback_why = (
        "it rewinds or shares a slot's rows, and this family's slots "
        "also hold the delta rule's matrix state and a conv's inputs, "
        "of which no snapshot is taken yet")

    def _model_fns(self) -> Tuple[Any, Any]:
        import jax
        import jax.numpy as jnp
        from ..gluon.model_zoo import olmo_hybrid as _oh
        from ..ops.pallas import column_write as _cw
        from ..ops.pallas import decode_attention as _da
        from ..ops.pallas import delta_step as _ds
        cfg, kinds = self.cfg, self.kinds
        heads, d = self.num_heads, self.head_dim
        scale = 1.0 / math.sqrt(d)
        nth = [sum(k == kind for k in kinds[:i])
               for i, kind in enumerate(kinds)]

        def _prefill(params, toks, t0):
            # toks (Lp,) padded past the traced real length t0.  Returns
            # the last real token's logits, the full layers' rows as
            # write_prompt takes them, and the linear layers' state at t0
            from jax import lax
            hidden, held = _oh.forward_sequence(params, toks, t0, cfg)
            h = lax.dynamic_slice_in_dim(hidden, t0 - 1, 1, axis=0)
            ks, vs = [], []
            state: Dict[str, List[Any]] = {"conv": [], "ssm": []}
            for kind, (a, b) in zip(kinds, held):
                if kind == "linear":
                    state["conv"].append(a)
                    state["ssm"].append(_ds.to_lanes(b))
                else:
                    ks.append(a)
                    vs.append(b)
            return _oh.lm_logits(params, h, cfg)[0], ks, vs, state

        def _logits(params, ks, vs, state, toks, pos):
            # the step's contract (model.DecodeModel._step): toks, pos
            # (S,), free slots ride along at pos 0.  ks/vs: the full
            # layers' rows; state: the linear layers' conv inputs and
            # matrix states
            S = pos.shape[0]
            with jax.named_scope("embed"):
                x = params["embed"][toks]
            new = {name: list(bufs) for name, bufs in state.items()}
            ks, vs = list(ks), list(vs)
            for kind, i, p in zip(kinds, nth, params["layers"]):
                if kind == "linear":
                    conv_in, gate, g, beta = _oh.linear_inputs(p, x, cfg)
                    c, new["conv"][i] = _oh.conv_step(
                        conv_in, new["conv"][i], p["conv_w"])
                    q, k, v = _oh.heads(c, cfg)
                    # every slot's state one token on, in place
                    with jax.named_scope("delta/state"):
                        o, new["ssm"][i] = _ds.delta_step(
                            new["ssm"][i], q, k, v, jnp.exp(g), beta)
                    x = _oh.linear_out(p, o, gate, x, cfg)
                else:
                    q, k, v = _oh.full_qkv(p, x, cfg)
                    # the token's K and V column of every slot, one
                    # in-place kernel call a layer, then each slot's
                    # live rows read by extent
                    with jax.named_scope("cache/write"):
                        ck, cv = _cw.write_columns(
                            (ks[i], vs[i]),
                            (k.reshape(S, heads * d), v.reshape(S, heads * d)),
                            pos)
                    ks[i], vs[i] = ck, cv
                    with jax.named_scope("attn/core"):
                        a = _da.ragged_attention(q.reshape(S, heads, 1, d),
                                                 ck, cv, pos, scale)
                    x = _oh.full_out(p, a.reshape(S, heads * d), x, cfg)
                x = _oh.mlp(p, x, cfg)
            return _oh.lm_logits(params, x, cfg), ks, vs, new

        return _prefill, _logits

    # -- what the engine asks of a family -----------------------------------
    def state_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """A linear layer's state of one slot, by name."""
        from ..gluon.model_zoo.olmo_hybrid import widths
        cfg = self.cfg
        return {"conv": (cfg["conv_size"] - 1, widths(cfg)["conv"]),
                "ssm": (cfg["key_dim"],
                        cfg["lin_heads"] * cfg["value_dim"])}

    def make_cache(self, max_slots: int, buckets: Sequence[int],
                   prefix_slots: Optional[int] = None,
                   prefix: Any = None) -> PagedKVCache:
        return PagedKVCache(
            self.n_layers, self.num_heads, self.head_dim, max_slots,
            buckets=buckets, dtype=self.dtype, prefix=prefix,
            prefix_slots=prefix_slots,
            kinds=[CACHE_KIND[k] for k in self.kinds],
            state_shapes=self.state_shapes())

    def row_blocks(self, positions: _np.ndarray,
                   bucket: int) -> Tuple[int, int]:
        """Every full layer reads its rows by the same extent."""
        from ..ops.pallas import decode_attention as _da
        read, every = _da.blocks_read(_np.asarray(positions), bucket)
        n = self.kinds.count("full")
        return n * read, n * every

    def describe(self) -> Dict[str, Any]:
        out = super().describe()
        cfg = self.cfg
        out.update(layer_kinds={k: self.kinds.count(k)
                                for k in dict.fromkeys(self.kinds)},
                   lin_heads=int(cfg["lin_heads"]),
                   key_dim=int(cfg["key_dim"]),
                   value_dim=int(cfg["value_dim"]),
                   chunk=int(cfg["chunk"]), max_prompt=self.max_prompt)
        return out
