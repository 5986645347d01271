"""Olmo Hybrid — the delta-rule hybrid decoder family (allenai
``Olmo-Hybrid-7B``, ``model_type`` ``olmo_hybrid``): Gated DeltaNet
layers beside full multi-head attention, in a repeating pattern
(``layer_types``: three ``linear_attention`` and one ``full_attention``
a period).

Every layer is Olmo 2/3's reordered block: the branch reads the stream
as it is and its OUTPUT is normed before it is added::

    x = x + RMSNorm(mixer(x));  x = x + RMSNorm(W_down(silu(W_gate x) * W_up x))

* ``linear`` (Gated DeltaNet, ``lin_heads`` heads): ``q, k`` of
  ``key_dim`` and ``v`` of ``value_dim`` a head from one projection,
  through a causal depthwise conv of ``conv_size`` taps and SiLU; ``q``
  and ``k`` L2-normed a head, ``q`` scaled by ``key_dim ** -0.5``; a
  decay ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))`` and a
  write strength ``beta = sigmoid(x W_b)`` (doubled, in (0, 2), where
  negative eigenvalues are allowed) a head and token; a float32 matrix
  state ``S (key_dim, value_dim)`` a head::

      S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
      o_t = S_t^T q_t

  then an RMSNorm over each head's ``value_dim`` (one gain shared by
  the heads) gated by ``silu(x W_g)``, and the output projection.
* ``full``: plain multi-head attention, heads of ``head_dim``, q and k
  RMS-normed over the whole width (Olmo 2/3's QK-norm), NO position
  embedding (``rope_theta`` is null), causal.

The head is untied.  Over a sequence the delta rule is evaluated in
chunks of ``chunk`` positions (:func:`delta_chunked`, the WY form: the
chunk's writes from the inverse of one unit lower-triangular matrix,
built by block merges, float32);
one token a slot at a time it is ``ops.pallas.delta_step``.

The math lives here once, as pure functions over a parameter pytree
(``_collect``): the zoo model's ``forward`` is one op around
``forward_logits``, ``serving.delta`` builds its prefill and decode
step from the same functions.  What ``config.json`` leaves open is
listed under ``assumed`` in ``chipbench/configs/olmo_hybrid_7b.json``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ... import initializer as _init
from ... import random as _random
from ...ndarray.ndarray import NDArray
from ...ops.nn import rms_norm_impl
from ..block import HybridBlock
from ..nn import HybridSequential
from ..parameter import Parameter
from .cohere2moe import _f32, _mm, _use_flash

__all__ = ["OlmoHybridModel", "get_olmo_hybrid", "layer_kinds",
           "forward_logits", "delta_chunked", "unit_lower_inverse"]

# the published config's names of the two kinds of layer
KIND_OF_TYPE = {"linear_attention": "linear", "full_attention": "full"}
# Olmo-Hybrid-7B's layer_types: three linear layers, then a full one
PERIOD = ("linear_attention",) * 3 + ("full_attention",)
_HIGHEST = lax.Precision.HIGHEST


def layer_kinds(num_layers: int,
                layer_types: Optional[Sequence[str]] = None) -> List[str]:
    """The mixer of each of the first ``num_layers`` layers: the
    published ``layer_types`` (the period repeated where none are
    given), cut to the depth held."""
    types = list(layer_types or PERIOD * -(-num_layers // len(PERIOD)))
    if len(types) < num_layers:
        raise ValueError(f"{len(types)} layer types for {num_layers} "
                         "layers")
    return [KIND_OF_TYPE[t] for t in types[:num_layers]]


# ---------------------------------------------------------------------------
# initialisers the family needs beside the registry's
# ---------------------------------------------------------------------------

class _ALog(_init.Initializer):
    """``A_log = log(A)``, ``A`` uniform in [1, 16] a head."""

    def _init(self, shape, dtype):
        a = jax.random.uniform(_random.split_key(), shape, jnp.float32,
                               1.0, 16.0)
        return jnp.log(a).astype(dtype)


class _DtBias(_init.Initializer):
    """The inverse softplus of a step drawn log-uniformly from
    [1e-3, 1e-1], a head."""

    def _init(self, shape, dtype):
        u = jax.random.uniform(_random.split_key(), shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """A linear layer's widths: ``qk`` (all heads' keys), ``conv`` (the
    conv's channels: q, k and v), ``value`` (all heads' values)."""
    h = cfg["lin_heads"]
    qk, value = h * cfg["key_dim"], h * cfg["value_dim"]
    return {"qk": qk, "value": value, "conv": 2 * qk + value}


class _Layer(HybridBlock):
    """One layer's parameters (the math is in the pure functions)."""

    def __init__(self, kind: str, cfg: Dict[str, Any], dtype: str,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self.kind = kind
        w, ffn = cfg["units"], cfg["hidden_size"]

        def mat(name, shape, init=None, dt=dtype):
            setattr(self, name, Parameter(
                name, shape=shape, dtype=dt,
                init=init or _init.Normal(0.02)))

        if kind == "linear":
            wd, h = widths(cfg), cfg["lin_heads"]
            # q, k and v (the conv's channels), the output gate, then
            # the decay's and the write strength's inputs, one matrix
            mat("in_w", (wd["conv"] + wd["value"] + 2 * h, w))
            mat("conv_w", (wd["conv"], cfg["conv_size"]),
                _init.Uniform(cfg["conv_size"] ** -0.5))
            mat("A_log", (h,), _ALog(), "float32")
            mat("dt_bias", (h,), _DtBias(), "float32")
            mat("o_norm_g", (cfg["value_dim"],), _init.One())
            mat("out_w", (w, wd["value"]))
        else:
            a = cfg["num_heads"] * cfg["head_dim"]
            mat("qkv_w", (3 * a, w))
            mat("q_norm_g", (a,), _init.One())
            mat("k_norm_g", (a,), _init.One())
            mat("out_w", (w, a))
        mat("post_mix_g", (w,), _init.One())
        mat("gate_up_w", (2 * ffn, w))
        mat("down_w", (w, ffn))
        mat("post_ff_g", (w,), _init.One())


class OlmoHybridModel(HybridBlock):
    """Decoder-only delta-rule hybrid LM: tokens (B, T) int -> logits
    (B, T, vocab) float32.  ``dtype`` is the dtype of the matrices and
    the activations; the recurrence, its state and parameters, RMSNorm,
    softmax and the logits are float32 whatever it is."""

    def __init__(self, vocab_size: int = 100352, num_layers: int = 32,
                 units: int = 3840, hidden_size: int = 11008,
                 num_heads: int = 30, head_dim: Optional[int] = None,
                 lin_heads: int = 30, key_dim: int = 96,
                 value_dim: int = 192, conv_size: int = 4,
                 allow_neg_eigval: bool = True,
                 layer_types: Optional[Sequence[str]] = None,
                 chunk: int = 64, rms_norm_eps: float = 1e-6,
                 max_length: int = 65536, dtype: str = "float32",
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        head_dim = head_dim or units // num_heads
        self.config = {
            "vocab_size": vocab_size, "num_layers": num_layers,
            "units": units, "hidden_size": hidden_size,
            "num_heads": num_heads, "head_dim": head_dim,
            "lin_heads": lin_heads, "key_dim": key_dim,
            "value_dim": value_dim, "conv_size": conv_size,
            "allow_neg_eigval": bool(allow_neg_eigval), "chunk": chunk,
            "rms_norm_eps": rms_norm_eps, "dtype": dtype,
            "kinds": layer_kinds(num_layers, layer_types),
        }
        self._max_length = max_length
        self.word_embed_weight = Parameter(
            "word_embed_weight", shape=(vocab_size, units), dtype=dtype,
            init=_init.Normal(0.02))
        self.layers = HybridSequential()
        for kind in self.config["kinds"]:
            self.layers.add(_Layer(kind, self.config, dtype))
        self.ln_f_g = Parameter("ln_f_g", shape=(units,), dtype=dtype,
                                init=_init.One())
        self.head_weight = Parameter(
            "head_weight", shape=(vocab_size, units), dtype=dtype,
            init=_init.Normal(0.02))

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
            params = _tree(dict(zip(names, flat)), cfg["kinds"])
            return jax.vmap(lambda t: forward_logits(params, t, cfg))(toks)

        return invoke("olmo_hybrid_forward", impl, [tokens] + arrays)


def _tree(flat: Dict[str, Any], kinds: Sequence[str]) -> Dict[str, Any]:
    """{attribute path: array} -> the pytree the pure functions take."""
    layers: List[Dict[str, Any]] = [{} for _ in kinds]
    for name, a in flat.items():
        if name.startswith("layers."):
            _, i, leaf = name.split(".")
            layers[int(i)][leaf] = a
    return {"embed": flat["word_embed_weight"], "lnf_g": flat["ln_f_g"],
            "head": flat["head_weight"], "layers": layers}


def _collect(model: OlmoHybridModel) -> Dict[str, Any]:
    """The model's parameters as the pure functions' pytree."""
    return _tree({name: jnp.asarray(p.data()._data)
                  for name, p in model.collect_params().items()},
                 model.config["kinds"])


_SPECS: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/allenai/Olmo-Hybrid-7B (the constructor's
    # defaults): 32 layers, 7.43 B parameters
    "olmo_hybrid_7b": {},
    # one of two 16-layer pipeline stages of it (four whole periods),
    # which one chip holds with a cache beside it: 4.10 B
    "olmo_hybrid_7b_stage": dict(num_layers=16),
    # CPU size: one period, two heads of each kind; a value width that
    # is no whole lane tile, as the published 192
    "tiny": dict(vocab_size=97, num_layers=4, units=64, hidden_size=96,
                 num_heads=2, lin_heads=2, key_dim=8, value_dim=48,
                 chunk=16, max_length=4096),
}


def get_olmo_hybrid(model_name: str = "olmo_hybrid_7b",
                    **kwargs: Any) -> OlmoHybridModel:
    if model_name not in _SPECS:
        raise ValueError(f"unknown Olmo Hybrid spec {model_name!r}; "
                         f"choose from {sorted(_SPECS)}")
    return OlmoHybridModel(**dict(_SPECS[model_name], **kwargs))


# ---------------------------------------------------------------------------
# pure math: x is (T, w) in the compute dtype (one sequence, or one
# token of each of T slots); p one layer's weights
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    """RMSNorm in float32 (``ops.nn.rms_norm``'s arithmetic), float32
    out."""
    return rms_norm_impl(_f32(x), _f32(g), eps=eps)


def residual(x, branch, g, eps):
    """``x + RMSNorm(branch)``: the block's norm is on the branch's
    OUTPUT (float32, as the product left it)."""
    return x + _rms(branch, g, eps).astype(x.dtype)


def mlp(p, x, cfg):
    """The MLP branch and its residual."""
    with jax.named_scope("ffn/up"):
        gate, up = jnp.split(_mm(x, p["gate_up_w"]), 2, axis=-1)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
    with jax.named_scope("ffn/down"):
        return residual(x, _mm(h, p["down_w"]), p["post_ff_g"],
                        cfg["rms_norm_eps"])


@jax.named_scope("attn/qkv")
def linear_inputs(p, x, cfg):
    """A linear layer's projections of ``x (T, w)``, float32 as the
    product leaves them: the conv's inputs ``(T, conv)``, the output
    gate ``(T, value)``, the log decay ``g = log alpha`` and ``beta``
    ``(T, heads)``.  Nothing is rounded to x's dtype on the way to the
    state, so the first linear layer's state is the float32 reference's
    to float32's rounding (what the on-chip check holds it to)."""
    wd, h = widths(cfg), cfg["lin_heads"]
    proj = _mm(x, p["in_w"])
    c, v = wd["conv"], wd["value"]
    conv_in = proj[:, :c]
    gate = proj[:, c:c + v]
    a, b = proj[:, c + v:c + v + h], proj[:, c + v + h:]
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(a + _f32(p["dt_bias"]))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg["allow_neg_eigval"] else 1.0)
    return conv_in, gate, g, beta


def _l2norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + eps)


def heads(c, cfg):
    """The conv's output ``(T, conv)`` float32 -> q (scaled), k, each
    ``(T, heads, key_dim)`` L2-normed a head, and v ``(T, heads,
    value_dim)``."""
    T, h, dk = c.shape[0], cfg["lin_heads"], cfg["key_dim"]
    qk = widths(cfg)["qk"]
    q = _l2norm(c[:, :qk].reshape(T, h, dk)) / math.sqrt(dk)
    k = _l2norm(c[:, qk:2 * qk].reshape(T, h, dk))
    return q, k, c[:, 2 * qk:].reshape(T, h, cfg["value_dim"])


@jax.named_scope("delta/conv")
def conv_seq(conv_in, w):
    """Causal depthwise conv and SiLU over a sequence: ``conv_in (T,
    C)`` -> ``(T, C)`` float32; output t reads inputs t - K + 1 .. t."""
    T, K = conv_in.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_in.shape[1]),
                                        jnp.float32), _f32(conv_in)])
    w = _f32(w)
    return jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))


@jax.named_scope("delta/conv")
def conv_tail(conv_in, t0, K):
    """The conv's state after the first ``t0`` inputs: inputs ``t0 - K +
    1 .. t0 - 1`` ``(K - 1, C)`` float32, zeros before position 0."""
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_in.shape[1]),
                                        jnp.float32), _f32(conv_in)])
    return lax.dynamic_slice_in_dim(padded, t0, K - 1, axis=0)


@jax.named_scope("delta/conv")
def conv_step(conv_in, state, w):
    """One input a slot: ``conv_in (S, C)``, ``state (S, K - 1, C)``
    float32 -> (the conv's SiLU'd output ``(S, C)``, the new state)."""
    taps = jnp.concatenate([state, _f32(conv_in)[:, None]], axis=1)
    w = _f32(w)
    out = sum(taps[:, j] * w[:, j] for j in range(w.shape[1]))
    return jax.nn.silu(out), taps[:, 1:]


def _mm_f32(spec, a, b):
    """``einsum`` of float32 operands at precision "highest"."""
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def unit_lower_inverse(A):
    """``(I + A)^-1`` for a strictly lower-triangular ``A (..., C, C)``
    float32, any ``C``, by block merges: the inverse's diagonal blocks
    double in size a level at a time from 1 x 1 blocks of 1, two
    neighbours whose inverses are ``T11`` and ``T22`` merging into
    ``[[T11, 0], [-T22 A21 T11, T22]]``.  That is forward substitution
    a block at a time: no power of ``A`` is formed, so nothing large
    cancels (a power series ``(I - A)(I + A^2)...`` does, at write
    strengths near 2 and keys that share a direction).  A level is two
    products of whole ``C x C`` matrices on the MXU, ``T - T X T`` with
    ``X`` the merging blocks' lower-left quadrants of ``A``: ``T`` is
    block-diagonal, so the only entries the products make are those
    quadrants' ``T22 A21 T11``, summed over the same terms as the blocks
    alone.  ``ceil(log2 C)`` levels (the first, onto blocks of 1, needs
    no product), float32 at precision "highest"."""
    i = jnp.arange(A.shape[-1])

    def lower_left(s):      # of the blocks of 2s, halves of s
        return ((i[:, None] // s == i[None, :] // s + 1)
                & (i[None, :] // s % 2 == 0))

    T = jnp.eye(A.shape[-1], dtype=A.dtype) - jnp.where(lower_left(1), A, 0.0)
    s = 2
    while s < A.shape[-1]:
        X = jnp.where(lower_left(s), A, 0.0)
        T = T - _mm_f32("...tj,...jk->...tk",
                        _mm_f32("...tj,...jk->...tk", T, X), T)
        s *= 2
    return T


@jax.named_scope("delta/chunk")
def delta_chunked(q, k, v, g, beta, chunk: int):
    """The delta rule over a sequence, ``chunk`` positions at a time:
    ``q``, ``k (T, H, dk)``, ``v (T, H, dv)``, ``g`` (log decay) and
    ``beta (T, H)``, float32, from ``S_0 = 0``.  Returns ``o (T, H,
    dv)`` and the state after the last position ``(H, dk, dv)``.

    Within a chunk, with ``G_t`` the decay from the chunk's start to
    ``t`` (``exp`` of the cumulated ``g``), the state is ``S_t = G_t S_0
    + sum_{j<=t} (G_t / G_j) k_j u_j^T`` whose writes ``u`` solve the
    unit lower-triangular ``(I + A) U = beta V - beta G K S_0``, ``A_tj
    = beta_t (G_t / G_j) k_t . k_j`` for ``j < t``.  So ``U = T (beta
    V) - T (beta G K) S_0``, ``o_t = G_t S_0^T q_t + sum_{j<=t} (G_t /
    G_j) (q_t . k_j) u_j`` and the chunk ends at ``G_C S_0 + sum_j
    (G_C / G_j) k_j u_j^T``: ``T = (I + A)^-1`` (block merges on the
    MXU, :func:`unit_lower_inverse`; no triangular solve, which the
    TPU's compiler runs as sequential work on the chunks' diagonal
    blocks), its products and the others that do not need ``S_0`` are
    made for every chunk at once, then a scan over the chunks carries
    the state.  Every product is float32 at precision "highest".  A
    position with ``beta`` 0 and ``g`` 0 leaves the state as it was:
    padding past a prompt's end does."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % chunk
    if pad:
        def zero_pad(a):
            return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        q, k, v, g, beta = map(zero_pad, (q, k, v, g, beta))
    n = (T + pad) // chunk

    def chunks(a):          # (T, H, ...) -> (n, H, C, ...)
        return jnp.swapaxes(a.reshape((n, chunk) + a.shape[1:]), 1, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    dec = jnp.cumsum(g, axis=-1)                        # (n, H, C)
    i = jnp.arange(chunk)
    on_or_below = i[:, None] >= i[None, :]
    below = i[:, None] > i[None, :]
    ratio = jnp.exp(jnp.where(on_or_below,
                              dec[..., :, None] - dec[..., None, :], 0.0))
    ratio = jnp.where(on_or_below, ratio, 0.0)          # G_t / G_j, j <= t

    mm = _mm_f32
    A = jnp.where(below, beta[..., :, None] * mm("nhtd,nhjd->nhtj", k, k)
                  * ratio, 0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(dec))[..., None] * k], axis=-1)
    sol = mm("nhtj,nhje->nhte", unit_lower_inverse(A), rhs)
    u, w = sol[..., :dv], sol[..., dv:]
    qk = mm("nhtd,nhjd->nhtj", q, k) * ratio
    qd = q * jnp.exp(dec)[..., None]
    kd = k * jnp.exp(dec[..., -1:] - dec)[..., None]
    last = jnp.exp(dec[..., -1])                         # (n, H)

    def body(S, xs):
        u, w, qk, qd, kd, last = xs
        fresh = u - mm("htd,hde->hte", w, S)
        o = mm("htd,hde->hte", qd, S) + mm("htj,hje->hte", qk, fresh)
        return last[:, None, None] * S + mm("htd,hte->hde", kd, fresh), o

    S, o = lax.scan(body, jnp.zeros((H, dk, dv), jnp.float32),
                    (u, w, qk, qd, kd, last))
    return jnp.swapaxes(o, 1, 2).reshape(n * chunk, H, dv)[:T], S


def linear_out(p, o, gate, x, cfg):
    """The rest of a linear layer from the heads' reads ``o (T, heads,
    value_dim)`` float32: the gated per-head RMSNorm, the output
    projection, the residual."""
    T = o.shape[0]
    with jax.named_scope("attn/out"):
        o = _rms(o, p["o_norm_g"], cfg["rms_norm_eps"]) \
            * jax.nn.silu(gate.reshape(o.shape))
        y = _mm(o.reshape(T, -1).astype(x.dtype), p["out_w"])
        return residual(x, y, p["post_mix_g"], cfg["rms_norm_eps"])


def linear_seq(p, x, t0, cfg):
    """A linear layer over one sequence ``x (T, w)`` whose real length
    is ``t0`` (traced; what lies past it changes nothing before it and
    not the state).  Returns (x after the layer, the conv's state and
    the delta state after ``t0`` positions)."""
    T = x.shape[0]
    conv_in, gate, g, beta = linear_inputs(p, x, cfg)
    real = (jnp.arange(T) < t0)[:, None]
    q, k, v = heads(conv_seq(conv_in, p["conv_w"]), cfg)
    o, S = delta_chunked(q, k, v, jnp.where(real, g, 0.0),
                         jnp.where(real, beta, 0.0), cfg["chunk"])
    tail = conv_tail(conv_in, t0, cfg["conv_size"])
    return linear_out(p, o, gate, x, cfg), tail, S


@jax.named_scope("attn/qkv")
def full_qkv(p, x, cfg):
    """The full layer's q, k, v ``(T, heads, head_dim)`` in x's dtype,
    q and k RMS-normed over the whole width; no position embedding."""
    T, d, eps = x.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    q, k, v = jnp.split(_mm(x, p["qkv_w"]), 3, axis=-1)
    q = _rms(q, p["q_norm_g"], eps).astype(x.dtype)
    k = _rms(k, p["k_norm_g"], eps).astype(x.dtype)
    return (q.reshape(T, -1, d), k.reshape(T, -1, d),
            v.astype(x.dtype).reshape(T, -1, d))


@jax.named_scope("attn/core")
def attention_seq(q, k, v):
    """Causal attention of T queries over the T rows before and at
    them, head ``n`` on head ``n``: (T, heads d) float32."""
    T, n, d = q.shape
    if _use_flash(T):
        from ...ops.pallas.attention import flash_attention
        out = flash_attention(q[None], k[None], v[None], causal=True)
        return _f32(out[0]).reshape(T, n * d)
    scores = jnp.einsum("tnd,snd->nts", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("nts,snd->tnd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).reshape(T, n * d)


def full_out(p, a, x, cfg):
    """The rest of a full layer from the heads' reads ``a (T, heads
    d)`` float32."""
    with jax.named_scope("attn/out"):
        return residual(x, _mm(a.astype(x.dtype), p["out_w"]),
                        p["post_mix_g"], cfg["rms_norm_eps"])


def forward_sequence(params, toks, t0, cfg):
    """One sequence ``toks`` (T,) of real length ``t0`` through every
    layer.  Returns the final hidden states ``(T, w)`` (before the final
    norm) and what each layer holds for a cache: a linear layer (its
    conv's state ``(K - 1, conv)``, its delta state ``(heads, dk,
    dv)``) after ``t0`` positions, a full layer its K and V rows
    ``(T, heads, d)``."""
    with jax.named_scope("embed"):
        x = params["embed"][toks]
    held = []
    for kind, p in zip(cfg["kinds"], params["layers"]):
        if kind == "linear":
            x, tail, S = linear_seq(p, x, t0, cfg)
            held.append((tail, S))
        else:
            q, k, v = full_qkv(p, x, cfg)
            x = full_out(p, attention_seq(q, k, v), x, cfg)
            held.append((k, v))
        x = mlp(p, x, cfg)
    return x, held


@jax.named_scope("head")
def lm_logits(params, hidden, cfg):
    """The final norm and the untied head, float32."""
    h = _rms(hidden, params["lnf_g"], cfg["rms_norm_eps"])
    return _mm(h.astype(hidden.dtype), params["head"])


def forward_logits(params, toks, cfg):
    """(T,) token ids -> (T, vocab) float32 logits."""
    hidden, _ = forward_sequence(params, toks, toks.shape[0], cfg)
    return lm_logits(params, hidden, cfg)
