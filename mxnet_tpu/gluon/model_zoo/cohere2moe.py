"""Command A+ — the ``cohere2_moe`` decoder family (CohereLabs,
``command-a-plus-05-2026``): a parallel block with sparse experts.

Every layer is ONE norm and three branches that read it::

    h = LayerNorm(x)                       # mean-centred, gain only
    y = x + attention(h) + routed(h) + shared(h)

* attention: grouped heads (``num_heads`` query heads of ``head_dim``,
  ``num_kv_heads`` K/V heads; query head ``n`` reads K/V head
  ``n // (num_heads / num_kv_heads)``), no biases.  Layers come in
  periods of ``layer_switch``: all but the last of a period are
  *window* layers (a query sees itself and the ``window - 1`` positions
  before it) and rotate q and k (interleaved RoPE over the whole head,
  absolute positions); the last is a *full* layer with no position
  embedding at all.
* routed: sigmoid scores over ``num_experts`` experts, the top
  ``top_k`` normalised to sum 1, each a gated (SwiGLU) FFN of width
  ``hidden_size``.  A model holds the experts ``experts_held = (lo,
  hi)`` of them, one chip's share of an expert-parallel deployment:
  the router keeps all its outputs, the held experts' part of the sum
  is computed and NOTHING stands in for an absent expert
  (``parallel.moe``).
* shared: ``num_shared`` always-on experts of the same shape, averaged.

The head is the tied embedding, of which ``vocab_rows`` rows are held
(a slice of the vocabulary is a smaller vocabulary).

The math lives here once, as pure functions over a parameter pytree
(``_collect``): the zoo model's ``forward`` is one op around
``forward_logits``, ``mxnet_tpu.serving.moe`` builds its prefill and
its decode step from the same functions.  What ``config.json`` leaves
open is listed under ``assumed`` in
``chipbench/configs/command_a_plus.json``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ... import initializer as _init
from ...ndarray.ndarray import NDArray
from ..block import HybridBlock
from ..nn import HybridSequential
from ..parameter import Parameter

__all__ = ["Cohere2MoEModel", "get_cohere2moe", "layer_kinds",
           "forward_logits"]


def layer_kinds(num_layers: int, layer_switch: int = 4) -> List[str]:
    """``window`` for every layer but the last of each period."""
    return ["full" if i % layer_switch == layer_switch - 1 else "window"
            for i in range(num_layers)]


class _Layer(HybridBlock):
    """One layer's parameters (the math is in the pure functions).
    Dense weights are (out, in); the experts' are stacked (expert, in,
    out), gate then up along ``out``, as the grouped product takes
    them."""

    def __init__(self, cfg: Dict[str, Any], dtype: str,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        w, f, d = cfg["units"], cfg["hidden_size"], cfg["head_dim"]
        q, kv = cfg["num_heads"] * d, cfg["num_kv_heads"] * d
        lo, hi = cfg["experts_held"]

        def mat(name, shape, init=None):
            setattr(self, name, Parameter(
                name, shape=shape, dtype=dtype,
                init=init or _init.Normal(0.02)))

        mat("ln_g", (w,), _init.One())
        mat("qkv_w", (q + 2 * kv, w))
        mat("out_w", (w, q))
        mat("router_w", (cfg["num_experts"], w))
        mat("expert_in", (hi - lo, w, 2 * f))
        mat("expert_out", (hi - lo, f, w))
        mat("shared_in", (cfg["num_shared"], w, 2 * f))
        mat("shared_out", (cfg["num_shared"], f, w))


class Cohere2MoEModel(HybridBlock):
    """Decoder-only LM: tokens (B, T) int -> logits (B, T, vocab_rows)
    in float32.  ``dtype`` is the dtype of the matrices and the
    activations; router scores, softmax, LayerNorm and the logits are
    float32 whatever it is."""

    def __init__(self, vocab_size: int = 262144,
                 vocab_rows: Optional[int] = None, num_layers: int = 32,
                 units: int = 4096, hidden_size: int = 4096,
                 num_heads: int = 128, num_kv_heads: int = 8,
                 head_dim: int = 128, num_experts: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None,
                 top_k: int = 8, num_shared: int = 4, window: int = 4096,
                 layer_switch: int = 4, rope_theta: float = 50000.0,
                 layer_norm_eps: float = 1e-5, logit_scale: float = 1.0,
                 max_length: int = 200000, dtype: str = "float32",
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        lo, hi = experts_held if experts_held is not None \
            else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(
                f"experts_held {experts_held!r} is no range of the "
                f"{num_experts} experts")
        if num_heads % num_kv_heads or head_dim % 2 \
                or top_k > num_experts:
            raise ValueError(
                f"{num_heads} query heads over {num_kv_heads} K/V heads "
                f"of {head_dim}, top {top_k} of {num_experts}: heads "
                "must group evenly, RoPE pairs the head's channels")
        rows = vocab_size if vocab_rows is None else vocab_rows
        self.config = {
            "vocab_size": vocab_size, "vocab_rows": rows,
            "num_layers": num_layers, "units": units,
            "hidden_size": hidden_size, "num_heads": num_heads,
            "num_kv_heads": num_kv_heads, "head_dim": head_dim,
            "num_experts": num_experts, "experts_held": (lo, hi),
            "top_k": top_k, "num_shared": num_shared, "window": window,
            "rope_theta": rope_theta, "layer_norm_eps": layer_norm_eps,
            "logit_scale": logit_scale, "dtype": dtype,
            "kinds": layer_kinds(num_layers, layer_switch),
        }
        self._max_length = max_length
        self.word_embed_weight = Parameter(
            "word_embed_weight", shape=(rows, units), dtype=dtype,
            init=_init.Normal(0.02))
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(_Layer(self.config, dtype))
        self.ln_f_g = Parameter("ln_f_g", shape=(units,), dtype=dtype,
                                init=_init.One())

    def num_parameters(self) -> int:
        """From the declared shapes alone: nothing is allocated."""
        return sum(math.prod(p.shape)
                   for p in self.collect_params().values())

    def forward(self, tokens: NDArray) -> NDArray:
        from ...ndarray.register import invoke
        names, arrays = [], []
        for name, p in self.collect_params().items():
            names.append(name)
            arrays.append(p.data())
        cfg = self.config

        def impl(toks, *flat):
            params = _tree(dict(zip(names, flat)), cfg["num_layers"])
            # one sequence at a time: the grouped product's segments
            # differ from sequence to sequence
            return lax.map(lambda t: forward_logits(params, t, cfg), toks)

        return invoke("cohere2moe_forward", impl, [tokens] + arrays)


def _tree(flat: Dict[str, Any], num_layers: int) -> Dict[str, Any]:
    """{attribute path: array} -> the pytree the pure functions take."""
    layers: List[Dict[str, Any]] = [{} for _ in range(num_layers)]
    for name, a in flat.items():
        if name.startswith("layers."):
            _, i, leaf = name.split(".")
            layers[int(i)][leaf] = a
    return {"embed": flat["word_embed_weight"], "lnf_g": flat["ln_f_g"],
            "layers": layers}


def _collect(model: Cohere2MoEModel) -> Dict[str, Any]:
    """The model's parameters as the pure functions' pytree."""
    return _tree({name: jnp.asarray(p.data()._data)
                  for name, p in model.collect_params().items()},
                 model.config["num_layers"])


_SPECS: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/CohereLabs/command-a-plus-05-2026 (the
    # constructor's defaults): 218 B, for its shapes and its parameter
    # count; no chip holds a layer
    "command_a_plus": {},
    # one chip's share where 8 chips share each layer (16 of the 128
    # routed experts; attention, shared experts, router and norms
    # whole), one period deep, an eighth of the vocabulary
    "command_a_plus_ep8": dict(num_layers=4, experts_held=(0, 16),
                               vocab_rows=32768),
    # CPU size with both kinds of layer and a share of the experts
    "tiny": dict(vocab_size=4096, vocab_rows=512, num_layers=4, units=64,
                 hidden_size=32, num_heads=8, num_kv_heads=2, head_dim=16,
                 num_experts=16, experts_held=(4, 8), top_k=4,
                 num_shared=2, window=8, max_length=4096),
}


def get_cohere2moe(model_name: str = "command_a_plus_ep8",
                   **kwargs: Any) -> Cohere2MoEModel:
    if model_name not in _SPECS:
        raise ValueError(f"unknown cohere2_moe spec {model_name!r}; "
                         f"choose from {sorted(_SPECS)}")
    return Cohere2MoEModel(**dict(_SPECS[model_name], **kwargs))


# ---------------------------------------------------------------------------
# pure math: x is (T, w) in the compute dtype (one sequence, or one
# token of each of T slots)
# ---------------------------------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


@jax.named_scope("norm")
def _ln(x, g, eps):
    """Bias-free LayerNorm in float32, handed back in x's dtype.  ONE
    feeds a layer's three branches, so it stands alone (``norm``)."""
    h = _f32(x)
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    return ((h - mean) * lax.rsqrt(var + eps) * _f32(g)).astype(x.dtype)


def _mm(x, w):
    """``x @ w.T`` accumulated in float32."""
    return jnp.einsum("...i,oi->...o", x, w,
                      preferred_element_type=jnp.float32)


def rope(x, pos, theta: float, pairing: str = "interleaved"):
    """RoPE over every head of ``x (T, heads, d)``, in float32: the
    channels of pair ``j`` turn by ``pos[t] theta^(-2 j / d)``.
    ``pairing`` says which two channels are pair ``j``: ``interleaved``
    ``(2 j, 2 j + 1)`` (this family; ``rope_gptj``), ``half``
    ``(j, j + d / 2)`` (the NeoX convention; ``model_zoo.ouro``).
    The pair's partner is fetched by a shift along the head's channels
    (a (.., d / 2, 2) view would put 2 on the lanes)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = _f32(pos)[:, None] * freq
    h = _f32(x)
    if pairing == "interleaved":
        angle = jnp.repeat(angle, 2, axis=-1)
        first = jnp.arange(d) % 2 == 0
        partner = jnp.where(first, -jnp.roll(h, -1, axis=-1),
                            jnp.roll(h, 1, axis=-1))
    elif pairing == "half":
        angle = jnp.concatenate([angle, angle], axis=-1)
        swapped = jnp.roll(h, d // 2, axis=-1)
        partner = jnp.where(jnp.arange(d) < d // 2, -swapped, swapped)
    else:
        raise ValueError(f"unknown RoPE pairing {pairing!r}")
    angle = angle[:, None, :]
    return (h * jnp.cos(angle) + partner * jnp.sin(angle)).astype(x.dtype)


@jax.named_scope("attn/qkv")
def qkv(p, h, pos, kind: str, cfg):
    """``h (T, w)`` -> q ``(T, heads, d)``, k and v ``(T, kv heads, d)``
    in h's dtype; q and k rotated at ``pos`` on a window layer, as the
    cache holds k."""
    d, nq, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    T = h.shape[0]
    out = _mm(h, p["qkv_w"]).astype(h.dtype)
    q = out[:, :nq * d].reshape(T, nq, d)
    k = out[:, nq * d:(nq + nkv) * d].reshape(T, nkv, d)
    v = out[:, (nq + nkv) * d:].reshape(T, nkv, d)
    if kind == "window":
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos,
                                                     cfg["rope_theta"])
    return q, k, v


def _use_flash(T: int) -> bool:
    # ops/transformer.py's rule for every family: the Pallas kernel from
    # 512 positions on, on an accelerator
    from ...ops.transformer import _use_pallas_len
    return _use_pallas_len(T)


@jax.named_scope("attn/core")
def attention_seq(q, k, v, cfg, window: Optional[int]):
    """Causal grouped attention of T queries over the T rows before and
    at them (``window`` of them where given): (T, heads d) float32."""
    T, nq, d = q.shape
    g = nq // k.shape[1]
    if _use_flash(T) and (window is None or window >= T):
        # the window cuts nothing of a sequence this short; K/V heads
        # repeated to the query heads' count for the kernel's (B, T, H,
        # D) operands
        from ...ops.pallas.attention import flash_attention
        out = flash_attention(q[None], jnp.repeat(k, g, axis=1)[None],
                              jnp.repeat(v, g, axis=1)[None], causal=True)
        return _f32(out[0]).reshape(T, nq * d)
    scores = jnp.einsum("tngd,snd->ngts", q.reshape(T, -1, g, d), k,
                        preferred_element_type=jnp.float32) \
        / math.sqrt(d)
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = s <= t
    if window is not None:
        keep &= s > t - window
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("ngts,snd->tngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(T, nq * d)


@jax.named_scope("experts/shared")
def shared_experts(p, h):
    """The mean of the always-on experts' outputs, (T, w) float32."""
    from ...parallel import moe as _moe
    n = p["shared_in"].shape[0]
    x = jnp.broadcast_to(h, (n,) + h.shape)
    act = _moe._swiglu(jnp.einsum("jtd,jdf->jtf", x, p["shared_in"],
                                  preferred_element_type=jnp.float32),
                       h.dtype)
    return jnp.einsum("jtf,jfd->td", act, p["shared_out"],
                      preferred_element_type=jnp.float32) / n


def experts(p, h, cfg, valid=None, grouped=True):
    """Both expert branches of a layer over ``h (T, w)``: (routed +
    shared (T, w) float32, the held experts' load (held,) int32).  The
    routed part by segments for a sequence's many tokens, as one
    batched product (``grouped=False``) for a decode step's few."""
    from ...parallel import moe as _moe
    local, weights, load, _ = _moe.route(
        h, p["router_w"], cfg["top_k"], cfg["experts_held"], valid)
    if grouped:
        routed = _moe.grouped_experts(h, local, weights, load,
                                      p["expert_in"], p["expert_out"])
    else:
        routed = _moe.dense_experts(h, local, weights, p["expert_in"],
                                    p["expert_out"])
    return routed + shared_experts(p, h), load


def forward_sequence(params, toks, t0, cfg):
    """One padded sequence ``toks`` (T,) of real length ``t0`` through
    every layer.  Returns the final hidden states (T, w), each layer's
    K (rotated where the layer rotates) and V rows (T, kv heads, d),
    and the held experts' load over the real tokens (layers, held)."""
    eps = cfg["layer_norm_eps"]
    T = toks.shape[0]
    pos = jnp.arange(T)
    valid = pos < t0
    with jax.named_scope("embed"):
        x = params["embed"][toks]
    rows, loads = [], []
    for kind, p in zip(cfg["kinds"], params["layers"]):
        h = _ln(x, p["ln_g"], eps)
        q, k, v = qkv(p, h, pos, kind, cfg)
        a = attention_seq(q, k, v, cfg,
                          cfg["window"] if kind == "window" else None)
        y, load = experts(p, h, cfg, valid)
        with jax.named_scope("attn/out"):
            a = _mm(a.astype(h.dtype), p["out_w"])
        x = x + (a + y).astype(x.dtype)
        rows.append((k, v))
        loads.append(load)
    with jax.named_scope("head"):
        return _ln(x, params["lnf_g"], eps), rows, jnp.stack(loads)


@jax.named_scope("head")
def lm_logits(params, hidden, cfg):
    return cfg["logit_scale"] * _mm(hidden, params["embed"])


def forward_logits(params, toks, cfg):
    """(T,) token ids -> (T, vocab_rows) float32 logits."""
    hidden, _, _ = forward_sequence(params, toks, toks.shape[0], cfg)
    return lm_logits(params, hidden, cfg)
