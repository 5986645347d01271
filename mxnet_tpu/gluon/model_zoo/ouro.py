"""Ouro — the looped decoder family (ByteDance ``Ouro-1.4B`` /
``Ouro-2.6B``, ``model_type`` ``ouro``; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): ONE stack of layers applied
``loop_steps`` times to every token, with the same weights each time.

A layer is a *sandwich*: RMSNorm before AND after each branch::

    x = x + RMSNorm(attention(RMSNorm(x)))
    x = x + RMSNorm(mlp(RMSNorm(x)))

* attention: plain multi-head (query head ``n`` reads K/V head ``n``),
  no biases, RoPE over the whole head with the half-split pairing
  ``(j, j + d / 2)`` at the absolute position, the same in every loop
  step.
* mlp: SwiGLU of width ``hidden_size``.

After the last layer of EVERY loop step comes the final RMSNorm; its
output ``z_t`` is both what step ``t + 1`` starts from and what the
exit gate ``sigmoid(z_t w + b)`` reads.  The gates give a distribution
over the step a token leaves at (:func:`exit_probabilities`); at the
published ``early_exit_threshold`` of 1 every token makes every step and
the untied head reads ``z`` of the last (:func:`exit_step`).

Pass ``(t, l)`` of a token attends the K and V that pass ``(t, l)`` of
the tokens before it made: a cache holds ``loop_steps x num_layers``
entries a position (``mxnet_tpu.serving.loop``).

The layers' weights are declared STACKED on a leading layer axis, so a
program is a loop over them (``lax.scan``) inside a loop over the steps,
whatever the depth.  The math lives here once, as pure functions over a
parameter pytree (``_collect``): the zoo model's ``forward`` is one op
around ``forward_logits``, ``serving.loop`` builds its prefill and its
decode step from the same functions.  What ``config.json`` leaves open
is listed under ``assumed`` in ``chipbench/configs/ouro_2_6b.json``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ... import initializer as _init
from ...ndarray.ndarray import NDArray
from ...ops.nn import rms_norm_impl
from ..block import HybridBlock
from ..parameter import Parameter
from .cohere2moe import _f32, _mm, _use_flash
from .cohere2moe import rope as _rope

__all__ = ["OuroModel", "get_ouro", "forward_logits", "exit_probabilities",
           "exit_step"]

# what a layer holds, by name: (shape behind the layer axis) from the
# configuration.  Dense weights are (out, in); q, k and v are one matrix
# (q's rows, then k's, then v's), gate and up another
_LAYER_SHAPES = {
    "qkv_w": lambda w, a, f: (3 * a, w),
    "out_w": lambda w, a, f: (w, a),
    "gate_up_w": lambda w, a, f: (2 * f, w),
    "down_w": lambda w, a, f: (w, f),
    # the four gains of the sandwich, in the order they are applied
    "norm_g": lambda w, a, f: (4, w),
}
# An untrained model's gains: 1 on a branch's input, BRANCH_GAIN on its
# output, small as deep residual stacks are initialised (LayerScale,
# ReZero): a token makes loop_steps x layers x 2 branch additions.  With
# 1 there the random looped map amplifies a perturbation 20-50 x from
# the first cache entry to the last (the final norm brings the state
# back to unit size, where the branches outweigh it): single positions
# of a bfloat16 server end half their size from a float32 reference
# and decisive tokens move.  With 1/4 and with 1/16 no token moved;
# some sequences' last entries still read 0.05-0.14 (PERF.md section 6,
# PR 35).  1 / 16 is exact in bfloat16.
BRANCH_GAIN = 0.0625
_GAINS = _init.Constant(_np.array([1.0, BRANCH_GAIN, 1.0, BRANCH_GAIN],
                                  _np.float32)[:, None])


class OuroModel(HybridBlock):
    """Decoder-only looped LM: tokens (B, T) int -> (logits (B, T,
    vocab) float32, exit probabilities (B, T, loop_steps) float32).
    ``dtype`` is the dtype of the matrices and the activations; RMSNorm,
    softmax, the gate and the logits are float32 whatever it is."""

    def __init__(self, vocab_size: int = 49152, num_layers: int = 48,
                 units: int = 2048, hidden_size: int = 5632,
                 num_heads: int = 16, head_dim: int = 128,
                 loop_steps: int = 4, exit_threshold: float = 1.0,
                 rope_theta: float = 1e6, rms_norm_eps: float = 1e-6,
                 max_length: int = 65536, dtype: str = "float32",
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        if head_dim % 2 or loop_steps < 1:
            raise ValueError(
                f"heads of {head_dim}, {loop_steps} loop steps: RoPE "
                "pairs the head's channels, and a token makes at least "
                "one step")
        self.config = {
            "vocab_size": vocab_size, "num_layers": num_layers,
            "units": units, "hidden_size": hidden_size,
            "num_heads": num_heads, "head_dim": head_dim,
            "loop_steps": loop_steps, "exit_threshold": exit_threshold,
            "rope_theta": rope_theta, "rms_norm_eps": rms_norm_eps,
            "dtype": dtype,
        }
        self._max_length = max_length

        def mat(name, shape, init=None):
            setattr(self, name, Parameter(
                name, shape=shape, dtype=dtype,
                init=init or _init.Normal(0.02)))

        mat("word_embed_weight", (vocab_size, units))
        for name, shape in _LAYER_SHAPES.items():
            mat(name, (num_layers,) + shape(units, num_heads * head_dim,
                                            hidden_size),
                _GAINS if name == "norm_g" else None)
        mat("ln_f_g", (units,), _init.One())
        mat("gate_w", (1, units))
        mat("gate_b", (1,), _init.Zero())
        mat("head_weight", (vocab_size, units))

    def num_parameters(self) -> int:
        """From the declared shapes alone: nothing is allocated."""
        return sum(math.prod(p.shape)
                   for p in self.collect_params().values())

    def forward(self, tokens: NDArray):
        from ...ndarray.register import invoke
        names, arrays = [], []
        for name, p in self.collect_params().items():
            names.append(name)
            arrays.append(p.data())
        cfg = self.config

        def impl(toks, *flat):
            params = _tree(dict(zip(names, flat)))
            return jax.vmap(lambda t: forward_logits(params, t, cfg))(toks)

        return invoke("ouro_forward", impl, [tokens] + arrays)


def _tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{parameter name: array} -> the pytree the pure functions take:
    ``layers`` holds every layer's weights stacked, as declared."""
    return {"embed": flat["word_embed_weight"], "head": flat["head_weight"],
            "lnf_g": flat["ln_f_g"], "gate_w": flat["gate_w"],
            "gate_b": flat["gate_b"],
            "layers": {name: flat[name] for name in _LAYER_SHAPES}}


def _collect(model: OuroModel) -> Dict[str, Any]:
    """The model's parameters as the pure functions' pytree."""
    return _tree({name: jnp.asarray(p.data()._data)
                  for name, p in model.collect_params().items()})


_SPECS: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/ByteDance/Ouro-2.6B (the constructor's
    # defaults): 2.67 B parameters, 48 layers x 4 loop steps
    "ouro_2_6b": {},
    # CPU size: a stack and a loop, both deeper than 2
    "tiny": dict(vocab_size=512, num_layers=3, units=64, hidden_size=96,
                 num_heads=4, head_dim=16, loop_steps=3, max_length=4096),
}


def get_ouro(model_name: str = "ouro_2_6b", **kwargs: Any) -> OuroModel:
    if model_name not in _SPECS:
        raise ValueError(f"unknown ouro spec {model_name!r}; "
                         f"choose from {sorted(_SPECS)}")
    return OuroModel(**dict(_SPECS[model_name], **kwargs))


# ---------------------------------------------------------------------------
# pure math: x is (T, w) in the compute dtype (one sequence, or one
# token of each of T slots); p one layer's weights
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    """RMSNorm in float32 (``ops.nn.rms_norm``'s arithmetic), float32
    out."""
    return rms_norm_impl(_f32(x), _f32(g), eps=eps)


def rope(x, pos, theta: float):
    """Half-split RoPE: channels ``(j, j + d / 2)`` turn together."""
    return _rope(x, pos, theta, pairing="half")


def residual(x, branch, g, eps):
    """``x + RMSNorm(branch)``: the sandwich's second norm is on the
    branch's OUTPUT (float32, as the product left it)."""
    return x + _rms(branch, g, eps).astype(x.dtype)


@jax.named_scope("attn/qkv")
def qkv(p, x, pos, cfg):
    """The attention branch up to its products: ``x (T, w)`` -> q, k, v
    ``(T, heads, d)`` in x's dtype, q and k rotated at ``pos``, as the
    cache holds k."""
    T, d = x.shape[0], cfg["head_dim"]
    h = _rms(x, p["norm_g"][0], cfg["rms_norm_eps"]).astype(x.dtype)
    q, k, v = jnp.split(
        _mm(h, p["qkv_w"]).astype(x.dtype).reshape(T, -1, d), 3, axis=1)
    theta = cfg["rope_theta"]
    return rope(q, pos, theta), rope(k, pos, theta), v


def finish(p, x, a, cfg):
    """The rest of a layer from the heads' reads ``a (T, heads d)``:
    the output projection and its norm, then the MLP between its two."""
    eps = cfg["rms_norm_eps"]
    with jax.named_scope("attn/out"):
        x = residual(x, _mm(a.astype(x.dtype), p["out_w"]),
                     p["norm_g"][1], eps)
    with jax.named_scope("ffn/up"):
        h = _rms(x, p["norm_g"][2], eps).astype(x.dtype)
        gate, up = jnp.split(_mm(h, p["gate_up_w"]), 2, axis=-1)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
    with jax.named_scope("ffn/down"):
        return residual(x, _mm(h, p["down_w"]), p["norm_g"][3], eps)


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


@jax.named_scope("norm")
def loop_output(params, x, t, cfg):
    """What loop step ``t`` hands on: the final norm's output, which is
    ``z_t`` and the state step ``t + 1`` starts from."""
    del t
    return _rms(x, params["lnf_g"], cfg["rms_norm_eps"]).astype(x.dtype)


def forward_sequence(params, toks, cfg):
    """One sequence ``toks`` (T,) through every loop step.  Returns
    every step's ``z`` ``(loop_steps, T, w)`` and every pass's K
    (rotated) and V rows ``(loop_steps, layers, T, heads, d)``.  Causal:
    what lies behind a position (padding) changes nothing before it."""
    pos = jnp.arange(toks.shape[0])

    def layer(x, p):
        q, k, v = qkv(p, x, pos, cfg)
        return finish(p, x, attention_seq(q, k, v), cfg), (k, v)

    def step(x, t):
        x, rows = lax.scan(layer, x, params["layers"])
        x = loop_output(params, x, t, cfg)
        return x, (x, rows)

    with jax.named_scope("embed"):
        x = params["embed"][toks]
    _, (z, (k, v)) = lax.scan(step, x, jnp.arange(cfg["loop_steps"]))
    return z, k, v


def exit_probabilities(params, z):
    """``z (loop_steps, T, w)`` -> (T, loop_steps) float32: the chance
    that a token leaves after step t: ``lam_t prod_{u<t} (1 - lam_u)``
    with ``lam_t = sigmoid(z_t w + b)``, the last step taking what is
    left."""
    lam = jax.nn.sigmoid(
        _mm(_f32(z), _f32(params["gate_w"]))[..., 0]
        + _f32(params["gate_b"]))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]]).T


def exit_step(probs, threshold: float):
    """(T,) int32: the first step whose cumulative exit probability
    reaches ``threshold``.  At the published threshold of 1 that is the
    last step for every token, whatever the gates say: no earlier step's
    cumulative probability is held to have reached it."""
    last = probs.shape[-1] - 1
    if threshold >= 1.0:
        return jnp.full(probs.shape[:-1], last, jnp.int32)
    reached = jnp.cumsum(probs, axis=-1) >= threshold
    return jnp.where(reached.any(-1), jnp.argmax(reached, axis=-1),
                     last).astype(jnp.int32)


@jax.named_scope("head")
def lm_logits(params, hidden):
    """The untied head, float32."""
    return _mm(hidden, params["head"])


def forward_logits(params, toks, cfg):
    """(T,) token ids -> (logits (T, vocab) float32 of the step
    :func:`exit_step` selects, exit probabilities (T, loop_steps))."""
    z, _, _ = forward_sequence(params, toks, cfg)
    probs = exit_probabilities(params, z)
    at = exit_step(probs, cfg["exit_threshold"])
    hidden = jnp.take_along_axis(z, at[None, :, None], axis=0)[0]
    return lm_logits(params, hidden), probs
