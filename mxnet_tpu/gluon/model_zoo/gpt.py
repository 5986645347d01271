"""GPT — decoder-only causal transformer language model.

Beyond-reference model family (the reference's NLP story was gluon-nlp
BERT, SURVEY.md section 2.5; the fork era predates decoder-only LMs as a
zoo staple) built from the same primitives: pre-LN blocks,
``npx.multi_head_attention(causal=True)`` (XLA attention, Pallas flash
kernel for long sequences, ring attention when the mesh has an 'sp'
axis), GELU FFN, weight-tied LM head. Works imperatively, hybridized,
and under SPMDTrainer (DEFAULT_TRANSFORMER_RULES name the qkv/out/ffn
parameters this model uses).
"""
from __future__ import annotations

from typing import Any, Optional

import jax

from ... import npx
from ... import numpy as mxnp
from ...ndarray.ndarray import NDArray
from ..block import HybridBlock
from ..nn import Dense, Embedding, HybridSequential, LayerNorm
from ..parameter import Parameter

__all__ = ["GPTBlock", "GPTModel", "get_gpt", "gpt2_124m"]


class GPTBlock(HybridBlock):
    """One pre-LN causal transformer block.

    ``moe_experts > 0`` replaces the dense FFN with a routed
    mixture-of-experts FFN (top-2 GShard gating by default): the
    pre-LN residual carries tokens an over-capacity expert drops —
    the Switch-Transformer integration pattern. Expert weights shard
    over the mesh's ``ep`` axis via MOE_TRANSFORMER_RULES.
    """

    def __init__(self, units: int = 768, hidden_size: int = 3072,
                 num_heads: int = 12, dropout: float = 0.1,
                 layer_norm_eps: float = 1e-5, moe_experts: int = 0,
                 moe_top_k: int = 2, moe_capacity_factor: float = 1.25,
                 moe_router_z_loss: float = 1e-3,
                 gelu_approximate: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._num_heads = num_heads
        # GPT-2 proper uses the tanh approximation ("gelu_new"); exact
        # erf GELU is the default here (and what BERT uses)
        self._gelu_approximate = gelu_approximate
        self.ln1 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.attn_qkv = Dense(3 * units, in_units=units, flatten=False)
        self.attn_out = Dense(units, in_units=units, flatten=False)
        self.ln2 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        if moe_experts > 0:
            from ...parallel.moe import MoEDense
            self.moe = MoEDense(moe_experts, hidden_size, units=units,
                                top_k=moe_top_k,
                                capacity_factor=moe_capacity_factor,
                                router_z_loss=moe_router_z_loss)
            self.ffn1 = self.ffn2 = None
        else:
            self.moe = None
            self.ffn1 = Dense(hidden_size, in_units=units, flatten=False)
            self.ffn2 = Dense(units, in_units=hidden_size, flatten=False)
        self._dropout = dropout

    def forward(self, x: NDArray) -> NDArray:
        # the scopes of tracing.COMPONENTS; pre-LN, so each LayerNorm
        # is the prologue of the projection after it
        with jax.named_scope("attn/qkv"):
            h = self.ln1(x)
            qkv = self.attn_qkv(h)
            q, k, v = mxnp.split(qkv, 3, axis=-1)
        att = npx.multi_head_attention(q, k, v, self._num_heads,
                                       causal=True,
                                       dropout=self._dropout)
        with jax.named_scope("attn/out"):
            att = self.attn_out(att)
            if self._dropout:
                att = npx.dropout(att, self._dropout)
            x = x + att

        def residual(ffn: NDArray) -> NDArray:
            if self._dropout:
                ffn = npx.dropout(ffn, self._dropout)
            return x + ffn

        if self.moe is not None:
            with jax.named_scope("experts"):
                return residual(self.moe(self.ln2(x)))
        with jax.named_scope("ffn/up"):
            ffn = npx.gelu(self.ffn1(self.ln2(x)),
                           approximate=self._gelu_approximate)
        with jax.named_scope("ffn/down"):
            return residual(self.ffn2(ffn))


class GPTModel(HybridBlock):
    """Decoder-only LM: tokens (B, T) int -> logits (B, T, vocab).

    The LM head is weight-tied to ``word_embed`` (standard GPT-2
    practice; also what DEFAULT_TRANSFORMER_RULES expects for
    vocab-parallel sharding of the embedding).
    """

    def __init__(self, vocab_size: int = 50257, num_layers: int = 12,
                 units: int = 768, hidden_size: int = 3072,
                 num_heads: int = 12, max_length: int = 1024,
                 dropout: float = 0.1, moe_every_n: int = 0,
                 moe_experts: int = 8, moe_top_k: int = 2,
                 moe_capacity_factor: float = 1.25,
                 moe_router_z_loss: float = 1e-3,
                 gelu_approximate: bool = False,
                 layer_norm_eps: float = 1e-5,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._units = units
        self._max_length = max_length
        self.word_embed = Embedding(vocab_size, units)
        self.position_weight = Parameter(
            "position_weight", shape=(max_length, units), init="normal")
        self.blocks = HybridSequential()
        for i in range(num_layers):
            # moe_every_n > 0: every n-th block swaps its dense FFN for a
            # routed expert FFN (GShard/ST-MoE interleaving)
            is_moe = moe_every_n > 0 and (i + 1) % moe_every_n == 0
            self.blocks.add(GPTBlock(units, hidden_size, num_heads,
                                     dropout,
                                     layer_norm_eps=layer_norm_eps,
                                     moe_experts=moe_experts if is_moe
                                     else 0,
                                     moe_top_k=moe_top_k,
                                     moe_capacity_factor=moe_capacity_factor,
                                     moe_router_z_loss=moe_router_z_loss,
                                     gelu_approximate=gelu_approximate))
        self.ln_f = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self._dropout = dropout

    def forward(self, tokens: NDArray) -> NDArray:
        T = tokens.shape[1]
        if T > self._max_length:
            from ...base import MXNetError
            raise MXNetError(
                f"sequence length {T} exceeds max_length "
                f"{self._max_length}")
        if not self.position_weight.is_initialized:
            self.position_weight._finish_deferred_init(
                (self._max_length, self._units))
        from ...ndarray import ops
        with jax.named_scope("embed"):
            x = self.word_embed(tokens)
            pos = ops.slice_axis(self.position_weight.data(), axis=0,
                                 begin=0, end=T)
            x = x + pos.expand_dims(0)
            if self._dropout:
                x = npx.dropout(x, self._dropout)
        # activation checkpointing per block under MXNET_REMAT
        from ..block import remat_stack
        x = remat_stack(list(self.blocks), x, dropout=self._dropout)
        with jax.named_scope("head"):
            x = self.ln_f(x)
            # weight-tied LM head: logits = x @ E^T
            w = self.word_embed.weight.data()
            return mxnp.matmul(x, w.T)

    def generate(self, tokens, max_new_tokens: int,
                 method: str = "greedy", temperature: float = 1.0,
                 top_k: int = 40, eos_token: Optional[int] = None,
                 seed: int = 0, top_p: float = 0.9) -> NDArray:
        """KV-cache incremental decoding (greedy / 'sample' / 'top_k' /
        'top_p' nucleus): one compiled prefill + lax.scan program per
        shape signature. See ``model_zoo.generation``."""
        from .generation import generate as _gen
        return _gen(self, tokens, max_new_tokens, method=method,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token=eos_token, seed=seed)

    def beam_search(self, tokens, max_new_tokens: int,
                    beam_size: int = 4,
                    eos_token: Optional[int] = None,
                    alpha: float = 1.0):
        """Length-normalized beam search over the KV-cache decoder
        (gluon-nlp BeamSearchSampler analog)."""
        from .generation import beam_search as _beam
        return _beam(self, tokens, max_new_tokens, beam_size=beam_size,
                     eos_token=eos_token, alpha=alpha)


_SPECS = {
    # name: (num_layers, units, hidden, heads, max_length)
    "gpt2_124m": (12, 768, 3072, 12, 1024),
    "gpt2_350m": (24, 1024, 4096, 16, 1024),
    "gpt2_774m": (36, 1280, 5120, 20, 1024),
}


def get_gpt(model_name: str = "gpt2_124m", vocab_size: int = 50257,
            dropout: float = 0.1, max_length: Optional[int] = None,
            **kwargs: Any) -> GPTModel:
    if model_name not in _SPECS:
        raise ValueError(
            f"unknown GPT spec {model_name!r}; choose from "
            f"{sorted(_SPECS)}")
    L, u, h, nh, ml = _SPECS[model_name]
    return GPTModel(vocab_size=vocab_size, num_layers=L, units=u,
                    hidden_size=h, num_heads=nh,
                    max_length=max_length or ml, dropout=dropout,
                    **kwargs)


def gpt2_124m(**kw: Any) -> GPTModel:
    return get_gpt("gpt2_124m", **kw)
