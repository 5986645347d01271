"""Phi-4-mini-flash — the SambaY decoder-hybrid family (arXiv:2507.06607).

Every layer is ``x += mixer(LN(x)); x += MLP(LN(x))`` with a gated MLP
and no position embedding; the mixer depends on the layer's index ``i``
(``layer_kinds``, ``mb_per_layer`` 2, ``L`` layers, half = ``L // 2``):

* ``mamba``  (i even, i <= half): Mamba-1 selective state space — a
  causal depthwise conv of 4 taps and a diagonal recurrence whose state
  ``(d_inner, d_state)`` is float32.  The last one (i = half) hands its
  output before the ``z`` gate on as the *memory*.
* ``window`` (i odd, i < half): differential attention over grouped
  K/V heads; a query sees itself and the ``window - 1`` positions
  before it.
* ``full``   (i = half + 1): the same attention over the whole context.
  Its K/V rows are the only ones that grow with the sequence.
* ``gmu``    (i even, i > half): Gated Memory Unit — the memory of the
  same position, gated by a projection of this layer's input.
* ``cross``  (i odd, i > half + 1): differential attention with this
  layer's queries over the ``full`` layer's K/V rows.

The math lives here once, as pure functions over a parameter pytree
(``_collect``): the zoo model's ``forward`` is one differentiable op
around ``forward_logits``, and ``mxnet_tpu.serving.hybrid`` builds its
prefill from the same sequence functions.  What ``config.json`` does
not give (the Mamba sizes, which layers are which, the heads' pairing,
``lambda_init``, the attention biases) is listed under ``assumed`` in
``chipbench/configs/phi4_mini_flash.json``.
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
from ..block import HybridBlock
from ..nn import HybridSequential
from ..parameter import Parameter

__all__ = ["Phi4FlashModel", "get_phi4flash", "layer_kinds",
           "lambda_init", "forward_logits"]

KINDS = ("mamba", "window", "full", "gmu", "cross")


def layer_kinds(num_layers: int, mb_per_layer: int = 2) -> List[str]:
    """The mixer of every layer, by index (module docstring)."""
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i % mb_per_layer == 0:
            kinds.append("mamba" if i <= half else "gmu")
        elif i < half:
            kinds.append("window")
        else:
            kinds.append("full" if i == half + 1 else "cross")
    return kinds


def lambda_init(depth: int) -> float:
    """Differential attention's ``lambda_init`` at layer ``depth``."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


# ---------------------------------------------------------------------------
# initialisers the family needs beside the registry's
# ---------------------------------------------------------------------------

class _ALog(_init.Initializer):
    """S4D-real: ``A = -(1 .. d_state)`` for every channel."""

    def _init(self, shape, dtype):
        row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).astype(dtype)


class _DtBias(_init.Initializer):
    """Mamba's ``dt`` bias: the inverse softplus of a step drawn
    log-uniformly from [1e-3, 1e-1]."""

    def _init(self, shape, dtype):
        u = jax.random.uniform(_random.split_key(), shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class _Layer(HybridBlock):
    """One layer's parameters (the math is in the pure functions)."""

    def __init__(self, kind: str, cfg: Dict[str, Any], dtype: str,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self.kind = kind
        w, ffn = cfg["units"], cfg["hidden_size"]
        di, n, r = cfg["d_inner"], cfg["d_state"], cfg["dt_rank"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        pair = 2 * cfg["head_dim"]

        def mat(name, shape, init=None, dt=dtype):
            setattr(self, name, Parameter(
                name, shape=shape, dtype=dt,
                init=init or _init.Normal(0.02)))

        for ln in ("ln1", "ln2"):
            mat(ln + "_g", (w,), _init.One())
            mat(ln + "_b", (w,), _init.Zero())
        mat("mlp_w1", (2 * ffn, w))
        mat("mlp_w2", (w, ffn))
        if kind == "mamba":
            mat("in_w", (2 * di, w))
            mat("conv_w", (di, cfg["d_conv"]), _init.Uniform(0.5))
            mat("conv_b", (di,), _init.Zero())
            mat("x_w", (r + 2 * n, di))
            mat("dt_w", (di, r), _init.Uniform(r ** -0.5))
            mat("dt_b", (di,), _DtBias(), "float32")
            mat("A_log", (di, n), _ALog(), "float32")
            mat("D", (di,), _init.One(), "float32")
            mat("out_w", (w, di))
        elif kind == "gmu":
            mat("in_w", (di, w))
            mat("out_w", (w, di))
        else:
            if kind == "cross":
                mat("q_w", (w, w))
                mat("q_b", (w,), _init.Zero())
            else:
                mat("qkv_w", (w + 2 * kv, w))
                mat("qkv_b", (w + 2 * kv,), _init.Zero())
            mat("out_w", (w, w))
            mat("out_b", (w,), _init.Zero())
            for lam in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
                mat(lam, (cfg["head_dim"],), _init.Normal(0.1), "float32")
            mat("subln_g", (pair,), _init.One())


class Phi4FlashModel(HybridBlock):
    """Decoder-only hybrid LM: tokens (B, T) int -> logits (B, T, vocab)
    in float32.  The head is tied to ``word_embed``; there is no
    position table.  ``dtype`` is the dtype of the matrices and the
    activations; the recurrence, its parameters and the logits are
    float32 whatever it is."""

    def __init__(self, vocab_size: int = 200064, num_layers: int = 32,
                 units: int = 2560, hidden_size: int = 10240,
                 num_heads: int = 40, num_kv_heads: int = 20,
                 window: int = 512, mb_per_layer: int = 2,
                 d_state: int = 16, d_conv: int = 4, expand: int = 2,
                 dt_rank: Optional[int] = None,
                 layer_norm_eps: float = 1e-5,
                 max_length: int = 262144, dtype: str = "float32",
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        if num_heads % 2 or num_kv_heads % 2 \
                or (num_heads // 2) % (num_kv_heads // 2):
            raise ValueError(
                f"differential attention pairs its heads: {num_heads} "
                f"query and {num_kv_heads} K/V heads do not pair up")
        self.config = {
            "vocab_size": vocab_size, "num_layers": num_layers,
            "units": units, "hidden_size": hidden_size,
            "num_heads": num_heads, "num_kv_heads": num_kv_heads,
            "head_dim": units // num_heads, "window": window,
            "mb_per_layer": mb_per_layer, "d_state": d_state,
            "d_conv": d_conv, "d_inner": expand * units,
            "dt_rank": dt_rank or math.ceil(units / 16),
            "layer_norm_eps": layer_norm_eps, "dtype": dtype,
            "kinds": layer_kinds(num_layers, mb_per_layer),
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
        self.ln_f_b = Parameter("ln_f_b", shape=(units,), dtype=dtype,
                                init=_init.Zero())

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

        return invoke("phi4flash_forward", impl, [tokens] + arrays)


def _tree(flat: Dict[str, Any], kinds: Sequence[str]) -> Dict[str, Any]:
    """{attribute path: array} -> the pytree the pure functions take."""
    layers: List[Dict[str, Any]] = [{} for _ in kinds]
    for name, a in flat.items():
        if name.startswith("layers."):
            _, i, leaf = name.split(".")
            layers[int(i)][leaf] = a
    return {"embed": flat["word_embed_weight"], "lnf_g": flat["ln_f_g"],
            "lnf_b": flat["ln_f_b"], "layers": layers}


def _collect(model: Phi4FlashModel) -> Dict[str, Any]:
    """The model's parameters as the pure functions' pytree (the twin of
    ``generation._collect`` for this family)."""
    return _tree({name: jnp.asarray(p.data()._data)
                  for name, p in model.collect_params().items()},
                 model.config["kinds"])


_SPECS: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
    "phi4_mini_flash": {},
    # CPU size that keeps all five kinds of layer
    "tiny": dict(vocab_size=503, num_layers=8, units=64, hidden_size=128,
                 num_heads=4, num_kv_heads=2, window=8, max_length=4096),
}


def get_phi4flash(model_name: str = "phi4_mini_flash",
                  **kwargs: Any) -> Phi4FlashModel:
    if model_name not in _SPECS:
        raise ValueError(f"unknown Phi-4-flash spec {model_name!r}; "
                         f"choose from {sorted(_SPECS)}")
    return Phi4FlashModel(**dict(_SPECS[model_name], **kwargs))


# ---------------------------------------------------------------------------
# pure math over one sequence: x is (T, w) in the compute dtype
# ---------------------------------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


@jax.named_scope("norm")
def _ln(x, g, b, eps):
    """LayerNorm computed in float32, handed back in x's dtype.  Its
    scope counts only where it is called outside every other component
    (tracing.COMPONENTS: the first word of a path is the component)."""
    h = _f32(x)
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    return ((h - mean) * lax.rsqrt(var + eps) * _f32(g)
            + _f32(b)).astype(x.dtype)


def _mm(x, w):
    """``x @ w.T`` accumulated in float32."""
    return jnp.einsum("...i,oi->...o", x, w,
                      preferred_element_type=jnp.float32)


def _mlp(p, x):
    with jax.named_scope("ffn/up"):
        gate, up = jnp.split(_mm(x, p["mlp_w1"]), 2, axis=-1)
        h = (up * jax.nn.silu(gate)).astype(x.dtype)
    with jax.named_scope("ffn/down"):
        return _mm(h, p["mlp_w2"]).astype(x.dtype)


def _ssm_inputs(p, uc, cfg):
    """The recurrence's float32 inputs from the conv's output ``uc``
    (..., d_inner): (dt, B, C)."""
    r, n = cfg["dt_rank"], cfg["d_state"]
    dbc = _mm(uc, p["x_w"])
    dt = jax.nn.softplus(_mm(dbc[..., :r].astype(uc.dtype), p["dt_w"])
                         + p["dt_b"])
    return dt, dbc[..., r:r + n], dbc[..., r + n:]


@jax.named_scope("ssm")
def _mamba_seq(p, x, t0, cfg, unroll: int = 8):
    """Mamba-1 over a sequence padded to ``T`` whose real length is the
    traced ``t0``: returns (y (T, w), memory m (T, d_inner) float32,
    conv tail (d_inner, d_conv - 1) and state (d_inner, d_state), both
    float32 and AT position ``t0 - 1``: ``dt`` is zeroed from ``t0``
    on, which leaves the state where it was)."""
    T, k = x.shape[0], cfg["d_conv"]
    u, z = jnp.split(_mm(x, p["in_w"]).astype(x.dtype), 2, axis=-1)
    padded = jnp.pad(_f32(u), ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + T] * _f32(p["conv_w"][:, j])
               for j in range(k)) + _f32(p["conv_b"])
    uc = jax.nn.silu(conv)
    dt, B, C = _ssm_inputs(p, uc.astype(x.dtype), cfg)
    dt = jnp.where((jnp.arange(T) < t0)[:, None], dt, 0.0)
    A = -jnp.exp(p["A_log"])

    def tick(h, inp):
        dt_t, u_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    h, hc = lax.scan(tick, jnp.zeros(A.shape, jnp.float32),
                     (dt, uc, B, C), unroll=unroll)
    m = hc + p["D"] * uc
    y = _mm((m * jax.nn.silu(_f32(z))).astype(x.dtype), p["out_w"])
    tail = lax.dynamic_slice_in_dim(padded, t0, k - 1, axis=0).T
    return y.astype(x.dtype), m, tail, h


def _lam(p, depth):
    return (jnp.exp(jnp.sum(p["lam_q1"] * p["lam_k1"]))
            - jnp.exp(jnp.sum(p["lam_q2"] * p["lam_k2"]))
            + lambda_init(depth))


def _diff_combine(p, a, depth, eps):
    """``a`` (..., 2, pair) float32, the two softmax maps' outputs:
    (1 - lambda_init) RMSNorm(a_1 - lambda a_2)."""
    d = a[..., 0, :] - _lam(p, depth) * a[..., 1, :]
    d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    return d * _f32(p["subln_g"]) * (1.0 - lambda_init(depth))


def _split_heads(q, k, v, cfg):
    """q (T, w) -> (T, kv pairs, query pairs a kv pair, 2, d);
    k (S, kv) -> (S, kv pairs, 2, d); v (S, kv) -> (S, kv pairs, 2 d)."""
    d, nkv = cfg["head_dim"], cfg["num_kv_heads"] // 2
    g = cfg["num_heads"] // cfg["num_kv_heads"]
    return (q.reshape(q.shape[0], nkv, g, 2, d),
            k.reshape(k.shape[0], nkv, 2, d),
            v.reshape(v.shape[0], nkv, 2 * d))


def _diff_attn_seq(p, q, k, v, depth, cfg, window: Optional[int]):
    """Differential attention of T queries over the T rows before and
    at them (``window`` rows where given); q (T, w), k and v (T, kv)."""
    T = q.shape[0]
    with jax.named_scope("attn/core"):
        qh, kh, vh = _split_heads(q, k, v, cfg)
        scores = jnp.einsum("tngjd,snjd->ngjts", qh, kh,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(cfg["head_dim"])
        t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        keep = s <= t
        if window is not None:
            keep &= s > t - window
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        a = jnp.einsum("ngjts,sne->tngje", probs.astype(v.dtype), vh,
                       preferred_element_type=jnp.float32)
    with jax.named_scope("attn/out"):
        out = _diff_combine(p, a, depth, cfg["layer_norm_eps"])
        return _mm(out.reshape(T, -1).astype(q.dtype), p["out_w"]) \
            + _f32(p["out_b"])


@jax.named_scope("attn/qkv")
def _qkv(p, x, cfg):
    w = cfg["units"]
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    qkv = (_mm(x, p["qkv_w"]) + _f32(p["qkv_b"])).astype(x.dtype)
    return qkv[..., :w], qkv[..., w:w + kv], qkv[..., w + kv:]


@jax.named_scope("ssm")
def _gmu(p, h, memory):
    """A Gated Memory Unit: the last Mamba layer's memory, gated."""
    gate = jax.nn.silu(_mm(h, p["in_w"]))
    return _mm((memory * gate).astype(h.dtype), p["out_w"])


@jax.named_scope("attn/qkv")
def _cross_q(p, h):
    """The query of a layer that reads the full layer's K and V."""
    return (_mm(h, p["q_w"]) + _f32(p["q_b"])).astype(h.dtype)


def forward_sequence(params, toks, t0, cfg):
    """One padded sequence ``toks`` (T,) of real length ``t0`` through
    every layer.  Returns the final hidden states (T, w) and what a
    cache needs of each layer, in layer order: ``("mamba", tail,
    state)``, ``("window" | "full", k, v)`` with all T rows, or
    ``None``."""
    eps = cfg["layer_norm_eps"]
    with jax.named_scope("embed"):
        x = params["embed"][toks]
    memory = shared = None
    cached: List[Any] = []
    for depth, (kind, p) in enumerate(zip(cfg["kinds"], params["layers"])):
        h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        entry = None
        if kind == "mamba":
            y, memory, tail, state = _mamba_seq(p, h, t0, cfg)
            entry = ("mamba", tail, state)
        elif kind == "gmu":
            y = _gmu(p, h, memory)
        elif kind == "cross":
            q = _cross_q(p, h)
            y = _diff_attn_seq(p, q, *shared, depth, cfg, None)
        else:
            q, k, v = _qkv(p, h, cfg)
            if kind == "full":
                shared = (k, v)
            y = _diff_attn_seq(p, q, k, v, depth, cfg,
                               cfg["window"] if kind == "window" else None)
            entry = (kind, k, v)
        cached.append(entry)
        x = x + y.astype(x.dtype)
        x = x + _mlp(p, _ln(x, p["ln2_g"], p["ln2_b"], eps))
    with jax.named_scope("head"):
        return _ln(x, params["lnf_g"], params["lnf_b"], eps), cached


def forward_logits(params, toks, cfg):
    """(T,) token ids -> (T, vocab) float32 logits."""
    hidden, _ = forward_sequence(params, toks, toks.shape[0], cfg)
    with jax.named_scope("head"):
        return _mm(hidden, params["embed"])
