"""HybridDecodeModel — the decode path of the Phi-4-mini-flash family
(``gluon.model_zoo.phi4flash``): layers of five kinds, and per slot
three kinds of state beside "nothing".

What a slot holds, by layer kind (``PagedKVCache`` allocates it):

* ``mamba``  -> ``state``: the conv's last ``d_conv - 1`` inputs
  ``(S, d_inner, d_conv - 1)`` and the recurrence's state
  ``(S, d_inner, d_state)``, float32, the same size at every position.
* ``window`` -> ``window``: K and V rows ``(S, kv, window)`` used as a
  ring: position ``p`` lives in column ``p % window``.  There is no
  position embedding and softmax does not care for the order of its
  keys, so the ring is never unrolled; a slot at position ``p`` sees
  the columns ``<= p``, which is all of them from ``window - 1`` on.
  (It also holds for keys stored ALREADY ROTATED at their absolute
  position, as ``serving.moe`` stores them: a score then depends on
  the two positions' difference alone.)
* ``full``   -> ``rows``: K and V rows ``(S, kv, L)`` in the bucket
  grid, written and grown exactly as the GPT family's.
* ``gmu``, ``cross`` -> nothing: a GMU layer reads the memory the last
  ``mamba`` layer made for the same token, a ``cross`` layer reads the
  ``full`` layer's rows.

Prefill is one program a prompt bucket, built from the zoo's sequence
functions; it hands back every kind AT THE PROMPT'S REAL LENGTH: the
recurrence stops there (``dt`` is zeroed past it), the conv tail and
the ring's columns are gathered from there.  The decode step is one
donated program a KV bucket over every slot; it writes the token's K/V
column of every slot with one kernel call a layer
(``ops.pallas.column_write``), reads the rings in full and the ``rows``
kind by extent, each slot's position blocks up to its own position
(``_rows_attention``).  Matrices and activations
are the block's dtype (bfloat16 as published); the recurrence, its
state, the softmax and the logits are float32.

Rolling a slot back (speculation) or sharing a prefix needs snapshots
of the recurrent state, which no one takes yet: ``GenerationEngine``
refuses both for this family, and ``verify`` / ``prefill_suffix``
raise.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .. import tracing as _tracing
from .kv_cache import PagedKVCache
from .model import DecodeModel, _sample_tokens, _select_one

__all__ = ["HybridDecodeModel"]

# the cache's kind of each kind of layer
CACHE_KIND = {"mamba": "state", "window": "window", "full": "rows",
              "gmu": "none", "cross": "none"}
# prompts are prefilled whole, one program a bucket, with dense masked
# attention: (heads, T, T) float32 scores bound the length one program
# can take beside the weights.  Chunked prefill lifts it (ROADMAP).
MAX_PROMPT = 1024
MIN_PROMPT_BUCKET = 64


def _slot_attention(p, q, ck, cv, pos, depth, cfg):
    """Differential attention of one query a slot, ``q`` (S, w), over
    that slot's columns ``<= pos`` of ``ck``/``cv`` (S, kv, L)."""
    import jax
    import jax.numpy as jnp
    from ..gluon.model_zoo import phi4flash as _pf
    S, L, d = q.shape[0], ck.shape[2], cfg["head_dim"]
    nkv = cfg["num_kv_heads"] // 2
    g = cfg["num_heads"] // cfg["num_kv_heads"]
    with jax.named_scope("attn/core"):
        # free views: d is whole sublane tiles (kv_cache module docstring)
        scores = jnp.einsum(
            "sngjd,snjdl->sngjl", q.reshape(S, nkv, g, 2, d),
            ck.reshape(S, nkv, 2, d, L),
            preferred_element_type=jnp.float32) / math.sqrt(d)
        visible = jnp.arange(L)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(visible[:, None, None, None, :],
                                         scores, -jnp.inf), axis=-1)
        a = jnp.einsum("sngjl,snel->sngje", probs.astype(cv.dtype),
                       cv.reshape(S, nkv, 2 * d, L),
                       preferred_element_type=jnp.float32)
    with jax.named_scope("attn/out"):
        out = _pf._diff_combine(p, a, depth, cfg["layer_norm_eps"])
        return _pf._mm(out.reshape(S, -1).astype(q.dtype), p["out_w"]) \
            + _pf._f32(p["out_b"])


def _rows_attention(p, q, ck, cv, pos, depth, cfg):
    """:func:`_slot_attention` over the ``rows`` kind by the ragged
    kernel (``ops.pallas.decode_attention``): of slot i's columns the
    position blocks up to ``pos[i]``'s are read and no other.  The
    window rings stay with the dense code: a ring is live in full from
    ``window - 1`` on."""
    import jax
    from ..gluon.model_zoo import phi4flash as _pf
    from ..ops.pallas import decode_attention as _da
    with jax.named_scope("attn/core"):
        a = _da.paired_decode_attention(q, ck, cv, pos, cfg["head_dim"])
    with jax.named_scope("attn/out"):
        out = _pf._diff_combine(p, a, depth, cfg["layer_norm_eps"])
        return _pf._mm(out.reshape(q.shape[0], -1).astype(q.dtype),
                       p["out_w"]) + _pf._f32(p["out_b"])


def _slot_mamba(p, x, conv, ssm, cfg):
    """One token a slot through a Mamba layer: ``x`` (S, w), ``conv``
    (S, d_inner, d_conv - 1) and ``ssm`` (S, d_inner, d_state) float32.
    Returns (y, memory, conv, ssm); the same operations in the same
    dtypes as ``phi4flash._mamba_seq``."""
    import jax
    import jax.numpy as jnp
    from ..gluon.model_zoo import phi4flash as _pf
    f32 = _pf._f32
    u, z = jnp.split(_pf._mm(x, p["in_w"]).astype(x.dtype), 2, axis=-1)
    taps = jnp.concatenate([conv, f32(u)[:, :, None]], axis=2)
    uc = jax.nn.silu(jnp.sum(taps * f32(p["conv_w"]), axis=2)
                     + f32(p["conv_b"]))
    dt, B, C = _pf._ssm_inputs(p, uc.astype(x.dtype), cfg)
    ssm = jnp.exp(dt[:, :, None] * -jnp.exp(p["A_log"])) * ssm \
        + (dt * uc)[:, :, None] * B[:, None, :]
    m = jnp.einsum("sdn,sn->sd", ssm, C) + p["D"] * uc
    y = _pf._mm((m * jax.nn.silu(f32(z))).astype(x.dtype), p["out_w"])
    return y.astype(x.dtype), m, taps[:, :, 1:], ssm


class HybridDecodeModel(DecodeModel):
    """``DecodeModel`` for a ``Phi4FlashModel``: the same surface the
    engine drives (``prefill``, ``step``, ``select``, ``warmup``), with
    the per-slot state carried as one pytree of the cache's buffers."""

    family = "phi4flash"
    max_prompt = MAX_PROMPT
    min_prompt_bucket = MIN_PROMPT_BUCKET
    supports_rollback = False
    no_rollback_why = (
        "it rewinds or shares a slot's rows, and this family's slots "
        "also hold recurrent state and window rings, of which no "
        "snapshot is taken yet")

    def __init__(self, params: Any, cfg: Dict[str, Any], max_length: int,
                 name: str) -> None:
        # not DecodeModel.__init__: that builds the GPT programs
        import jax
        import jax.numpy as jnp
        from ..gluon.model_zoo import phi4flash as _pf
        from ..ops.pallas import column_write as _cw
        self.params = params
        self.cfg = cfg
        self.kinds = list(cfg["kinds"])
        self.max_length = int(max_length)
        self.name = name
        self.vocab_size, self.units = params["embed"].shape
        self.num_heads = int(cfg["num_heads"])
        self.head_dim = int(cfg["head_dim"])
        self.n_layers = len(self.kinds)
        self.dtype = params["embed"].dtype
        self.logits_dtype = jnp.dtype(jnp.float32)
        self._seen_lock = threading.Lock()
        self._seen: set = set()
        W = int(cfg["window"])
        kinds = self.kinds
        nth = [sum(CACHE_KIND[k] == CACHE_KIND[kind] for k in kinds[:i])
               for i, kind in enumerate(kinds)]

        def _prefill(params, toks, t0):
            # toks (Lp,) padded past the traced real length t0.  Returns
            # the last real token's logits, the full layer's rows as
            # write_prompt takes them, and the fixed-size kinds at t0
            from jax import lax
            Lp = toks.shape[0]
            hidden, cached = _pf.forward_sequence(params, toks, t0, cfg)
            with jax.named_scope("head"):
                h = lax.dynamic_slice_in_dim(hidden, t0 - 1, 1, axis=0)[0]
                logits = _pf._mm(h, params["embed"])
            # ring column j holds the newest position < t0 that is
            # congruent to j; columns past t0 - 1 hold no position yet
            # and stay invisible until the step writes them
            j = jnp.arange(W)
            newest = jnp.clip(j + W * ((t0 - 1 - j) // W), 0, Lp - 1)
            ks, vs = [], []
            state: Dict[str, List[Any]] = {
                "wk": [], "wv": [], "conv": [], "ssm": []}
            for entry in cached:
                if entry is None:
                    continue
                kind, a, b = entry
                if kind == "mamba":
                    state["conv"].append(a)
                    state["ssm"].append(b)
                elif kind == "window":
                    with jax.named_scope("cache/write"):
                        state["wk"].append(a[newest].T)
                        state["wv"].append(b[newest].T)
                else:
                    ks.append(a.reshape(Lp, -1, cfg["head_dim"]))
                    vs.append(b.reshape(Lp, -1, cfg["head_dim"]))
            return logits, ks, vs, state

        def _step(params, ks, vs, state, toks, pos, seeds, bases, temps,
                  topks, topps, methods):
            # the GPT step's contract (model.DecodeModel._step): toks,
            # pos (S,), free slots ride along at pos 0, the sampler in
            # the program.  ks/vs: the full layer's rows; state: the
            # window rings, conv tails and recurrence states
            from jax import lax
            eps = cfg["layer_norm_eps"]
            with jax.named_scope("embed"):
                x = params["embed"][toks]
            ring = pos % W
            seen_ring = jnp.minimum(pos, W - 1)
            new = {name: list(bufs) for name, bufs in state.items()}
            ks, vs = list(ks), list(vs)
            memory = None
            for depth, (kind, p) in enumerate(zip(kinds,
                                                  params["layers"])):
                h = _pf._ln(x, p["ln1_g"], p["ln1_b"], eps)
                i = nth[depth]      # among the layers of its cache kind
                if kind == "mamba":
                    with jax.named_scope("ssm"):
                        y, memory, new["conv"][i], new["ssm"][i] = \
                            _slot_mamba(p, h, new["conv"][i],
                                        new["ssm"][i], cfg)
                elif kind == "gmu":
                    y = _pf._gmu(p, h, memory)
                elif kind == "cross":
                    y = _rows_attention(p, _pf._cross_q(p, h), ks[0],
                                        vs[0], pos, depth, cfg)
                else:
                    q, k, v = _pf._qkv(p, h, cfg)
                    # the token's K and V column of every slot, one
                    # in-place kernel call a layer: a ring's at pos % W,
                    # a row's at pos
                    if kind == "window":
                        with jax.named_scope("cache/write"):
                            ck, cv = _cw.write_columns(
                                (new["wk"][i], new["wv"][i]), (k, v), ring)
                        new["wk"][i], new["wv"][i] = ck, cv
                        y = _slot_attention(p, q, ck, cv, seen_ring,
                                            depth, cfg)
                    else:
                        with jax.named_scope("cache/write"):
                            ck, cv = _cw.write_columns((ks[i], vs[i]),
                                                       (k, v), pos)
                        ks[i], vs[i] = ck, cv
                        y = _rows_attention(p, q, ck, cv, pos, depth, cfg)
                x = x + y.astype(x.dtype)
                x = x + _pf._mlp(p, _pf._ln(x, p["ln2_g"], p["ln2_b"],
                                            eps))
            with jax.named_scope("head"):
                x = _pf._ln(x, params["lnf_g"], params["lnf_b"], eps)
                logits = _pf._mm(x, params["embed"])

            def _mixed(lg):
                return _sample_tokens(lg, seeds, pos - bases, temps,
                                      topks, topps, methods)

            def _greedy(lg):
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)

            with jax.named_scope("sample"):
                next_tok = lax.cond(jnp.any(methods != 0), _mixed,
                                    _greedy, logits)
            return next_tok, ks, vs, new

        fam = self.family
        self._prefill_fn = _tracing.program(_prefill, "prefill", fam)
        self._select_fn = _tracing.program(_select_one, "select", fam)
        self._step_fn = _tracing.program(_step, "decode", fam,
                                         donate_argnums=(1, 2, 3))

    @staticmethod
    def from_phi4flash(block: Any) -> "HybridDecodeModel":
        from ..gluon.model_zoo.phi4flash import _collect
        return HybridDecodeModel(_collect(block), dict(block.config),
                                 block._max_length, type(block).__name__)

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
            window=cfg["window"],
            state_shapes={"conv": (cfg["d_inner"], cfg["d_conv"] - 1),
                          "ssm": (cfg["d_inner"], cfg["d_state"])})

    def row_blocks(self, positions: _np.ndarray,
                   bucket: int) -> Tuple[int, int]:
        from ..ops.pallas import decode_attention as _da
        return _da.blocks_read(_np.asarray(positions), bucket)

    # -- execution: DecodeModel's prefill and step, which hand a family's
    # extra results through (the fixed-size kinds ride fourth) ----------
    def verify(self, *args: Any, **kwargs: Any) -> _np.ndarray:
        raise self.no_rollback("speculative verification")

    def prefill_suffix(self, *args: Any, **kwargs: Any) -> Any:
        raise self.no_rollback("suffix prefill over a shared prefix")

    def describe(self) -> Dict[str, Any]:
        out = super().describe()
        out.update(layer_kinds={k: self.kinds.count(k)
                                for k in dict.fromkeys(self.kinds)},
                   window=int(self.cfg["window"]),
                   max_prompt=self.max_prompt)
        return out
