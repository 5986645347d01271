"""BERT — transformer encoder + pretraining heads.

Reference parity: gluon-nlp's BERTModel (the model behind the reference's
``src/operator/contrib/transformer.cc`` interleaved-attention ops; BASELINE
config 3). Architecture: embeddings (word+position+token-type, layernorm,
dropout), N transformer layers (pre/post-LN, GELU FFN), pooler, MLM and
NSP heads with tied decoder weights.

TPU-first: attention goes through ``npx.multi_head_attention`` (XLA fused;
Pallas flash kernel for long sequences), bf16-friendly throughout.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Tuple

import jax

from ... import npx
from ... import numpy as mxnp
from ...ndarray.ndarray import NDArray
from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm
from ..parameter import Parameter

__all__ = ["BERTEncoderLayer", "BERTEncoder", "BERTModel", "get_bert",
           "bert_base", "bert_large"]


class BERTEncoderLayer(HybridBlock):
    """One transformer layer (post-LN like BERT)."""

    def __init__(self, units: int = 768, hidden_size: int = 3072,
                 num_heads: int = 12, dropout: float = 0.1,
                 layer_norm_eps: float = 1e-12, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._num_heads = num_heads
        self._units = units
        self.attn_qkv = Dense(3 * units, in_units=units, flatten=False)
        self.attn_out = Dense(units, in_units=units, flatten=False)
        self.ln1 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ffn1 = Dense(hidden_size, in_units=units, flatten=False)
        self.ffn2 = Dense(units, in_units=hidden_size, flatten=False)
        self.ln2 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self._dropout = dropout

    def forward(self, x: NDArray, mask: Optional[NDArray] = None) -> NDArray:
        # the scopes of tracing.COMPONENTS; post-LN, so each LayerNorm
        # is the epilogue of the projection before it
        with jax.named_scope("attn/qkv"):
            qkv = self.attn_qkv(x)  # (B, T, 3C)
            q, k, v = mxnp.split(qkv, 3, axis=-1)
        att = npx.multi_head_attention(q, k, v, self._num_heads, mask=mask,
                                       dropout=self._dropout)
        with jax.named_scope("attn/out"):
            att = self.attn_out(att)
            if self._dropout:
                att = npx.dropout(att, self._dropout)
            x = self.ln1(x + att)
        with jax.named_scope("ffn/up"):
            ffn = npx.gelu(self.ffn1(x))
        with jax.named_scope("ffn/down"):
            ffn = self.ffn2(ffn)
            if self._dropout:
                ffn = npx.dropout(ffn, self._dropout)
            return self.ln2(x + ffn)


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers: int = 12, units: int = 768,
                 hidden_size: int = 3072, num_heads: int = 12,
                 max_length: int = 512, dropout: float = 0.1,
                 layer_norm_eps: float = 1e-12,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._max_length = max_length
        self._units = units
        self.position_weight = Parameter("position_weight",
                                         shape=(max_length, units),
                                         init="normal")
        self.ln = LayerNorm(in_channels=units, epsilon=layer_norm_eps)
        self._dropout = dropout
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(BERTEncoderLayer(units, hidden_size, num_heads,
                                             dropout,
                                             layer_norm_eps=layer_norm_eps))

    def forward(self, x: NDArray, mask: Optional[NDArray] = None) -> NDArray:
        if not self.position_weight.is_initialized:
            self.position_weight._finish_deferred_init(
                (self._max_length, self._units))
        T = x.shape[1]
        from ...ndarray import ops
        with jax.named_scope("embed"):
            pos = ops.slice_axis(self.position_weight.data(), axis=0,
                                 begin=0, end=T)
            x = x + pos.expand_dims(0)
            x = self.ln(x)
            if self._dropout:
                x = npx.dropout(x, self._dropout)
        # activation checkpointing per layer under MXNET_REMAT
        from ..block import remat_stack
        return remat_stack(list(self.layers), x, mask,
                           dropout=self._dropout)


class BERTModel(HybridBlock):
    """Full BERT with MLM + NSP heads (gluon-nlp BERTModel parity).

    ``forward(inputs, token_types, valid_length, masked_positions)``:
      - no ``masked_positions``: returns (sequence_output, pooled_output)
      - with ``masked_positions``: additionally returns MLM logits.
    """

    def __init__(self, vocab_size: int = 30522, num_layers: int = 12,
                 units: int = 768, hidden_size: int = 3072,
                 num_heads: int = 12, max_length: int = 512,
                 token_type_vocab_size: int = 2, dropout: float = 0.1,
                 use_pooler: bool = True, use_decoder: bool = True,
                 use_classifier: bool = True,
                 layer_norm_eps: float = 1e-12,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._units = units
        self.word_embed = Embedding(vocab_size, units)
        self.token_type_embed = Embedding(token_type_vocab_size, units)
        self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                   max_length, dropout,
                                   layer_norm_eps=layer_norm_eps)
        self.pooler = Dense(units, in_units=units, flatten=False,
                            activation="tanh") if use_pooler else None
        if use_decoder:
            # MLM head: transform + layernorm + decode (weights tied to
            # word embedding, reference-style)
            self.mlm_transform = Dense(units, in_units=units, flatten=False)
            self.mlm_ln = LayerNorm(in_channels=units,
                                    epsilon=layer_norm_eps)
            self.mlm_bias = Parameter("mlm_bias", shape=(vocab_size,),
                                      init="zeros")
        else:
            self.mlm_transform = None
        self.classifier = Dense(2, in_units=units) if use_classifier else None

    def _attention_mask(self, inputs: NDArray,
                        valid_length: Optional[NDArray]):
        if valid_length is None:
            return None
        B, T = inputs.shape[:2]
        from ...ndarray.ops import _as_nd
        from ...ndarray.register import invoke

        def impl(vl):
            import jax.numpy as jnp
            ar = jnp.arange(T)
            keep = ar[None, :] < vl[:, None].astype(jnp.int32)  # (B, Tk)
            return keep[:, None, None, :]  # (B, 1, 1, Tk)
        return invoke("bert_mask", impl, (_as_nd(valid_length),))

    def forward(self, inputs: NDArray,
                token_types: Optional[NDArray] = None,
                valid_length: Optional[NDArray] = None,
                masked_positions: Optional[NDArray] = None):
        with jax.named_scope("embed"):
            x = self.word_embed(inputs)
            if token_types is not None:
                x = x + self.token_type_embed(token_types)
            mask = self._attention_mask(inputs, valid_length)
        seq = self.encoder(x, mask)

        outputs: List[Any] = [seq]
        if self.pooler is not None:
            from ...ndarray import ops
            with jax.named_scope("head"):
                cls = ops.slice_axis(seq, axis=1, begin=0,
                                     end=1).squeeze(1)
                outputs.append(self.pooler(cls))
        if self.mlm_transform is not None and masked_positions is not None:
            if not self.mlm_bias.is_initialized:
                self.mlm_bias._finish_deferred_init(self.mlm_bias.shape)
            with jax.named_scope("head"):
                gathered = npx.take_positions(seq, masked_positions)
                h = npx.gelu(self.mlm_transform(gathered))
                h = self.mlm_ln(h)
                logits = mxnp.dot(h.reshape(-1, self._units),
                                  self.word_embed.weight.data().T)
                logits = logits + self.mlm_bias.data()
                logits = logits.reshape(gathered.shape[0],
                                        gathered.shape[1], -1)
            outputs.append(logits)
        return tuple(outputs) if len(outputs) > 1 else outputs[0]


_BERT_SPEC = {
    "bert_12_768_12": dict(num_layers=12, units=768, hidden_size=3072,
                           num_heads=12),
    "bert_24_1024_16": dict(num_layers=24, units=1024, hidden_size=4096,
                            num_heads=16),
}


def get_bert(model_name: str = "bert_12_768_12", vocab_size: int = 30522,
             **kwargs: Any) -> BERTModel:
    from ...base import MXNetError
    if model_name not in _BERT_SPEC:
        raise MXNetError(f"unknown bert spec {model_name!r}; "
                         f"options: {sorted(_BERT_SPEC)}")
    cfg = dict(_BERT_SPEC[model_name])
    cfg.update(kwargs)
    return BERTModel(vocab_size=vocab_size, **cfg)


def bert_base(**kw) -> BERTModel:
    return get_bert("bert_12_768_12", **kw)


def bert_large(**kw) -> BERTModel:
    return get_bert("bert_24_1024_16", **kw)
