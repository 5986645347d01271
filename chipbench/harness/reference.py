"""The plain reference: a transformer forward pass in straightforward
float32 jax.numpy, matmul precision "highest", no kernels, no cache, no
batching tricks.  GPT-2 and BERT differ only in where the LayerNorm
sits, the mask, the segment embedding and the head.

Parameters are a dict in the layout of
``mxnet_tpu.gluon.model_zoo.generation._collect``: ``embed`` (V, w),
``pos`` (P, w), ``blocks`` (a list of dicts with ln1_g/b, qkv_w/b,
out_w/b, ln2_g/b, f1_w/b, f2_w/b; Dense weights are (out, in)), and
optionally ``type_embed``, ``emb_ln_g/b`` (BERT), ``lnf_g/b`` (GPT-2),
``mlm_w/b``, ``mlm_ln_g/b``, ``mlm_bias`` (the MLM head).
"""
import jax
import jax.numpy as jnp


def to_float32(params):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)


def _ln(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _attention(p, x, num_heads, causal):
    B, T, C = x.shape
    d = C // num_heads
    qkv = x @ p["qkv_w"].T + p["qkv_b"]
    q, k, v = (t.reshape(B, T, num_heads, d)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        keep = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, C)
    return out @ p["out_w"].T + p["out_b"]


def _ffn(p, x, gelu_approx):
    h = jax.nn.gelu(x @ p["f1_w"].T + p["f1_b"], approximate=gelu_approx)
    return h @ p["f2_w"].T + p["f2_b"]


def hidden_states(params, ids, *, num_heads, causal, pre_ln, eps,
                  gelu_approx, segments=None):
    """(B, T) token ids -> (B, T, w) final hidden states."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[1]
        x = params["embed"][ids] + params["pos"][:T]
        if "type_embed" in params:
            x = x + params["type_embed"][segments]
        if "emb_ln_g" in params:
            x = _ln(x, params["emb_ln_g"], params["emb_ln_b"], eps)
        for p in params["blocks"]:
            if pre_ln:      # GPT-2: normalise, transform, add
                x = x + _attention(p, _ln(x, p["ln1_g"], p["ln1_b"], eps),
                                   num_heads, causal)
                x = x + _ffn(p, _ln(x, p["ln2_g"], p["ln2_b"], eps),
                             gelu_approx)
            else:           # BERT: transform, add, normalise
                x = _ln(x + _attention(p, x, num_heads, causal),
                        p["ln1_g"], p["ln1_b"], eps)
                x = _ln(x + _ffn(p, x, gelu_approx),
                        p["ln2_g"], p["ln2_b"], eps)
        if "lnf_g" in params:
            x = _ln(x, params["lnf_g"], params["lnf_b"], eps)
        return x


def lm_logits(params, hidden):
    """Tied output head: (..., w) -> (..., V)."""
    with jax.default_matmul_precision("highest"):
        return hidden @ params["embed"].T


def mlm_logits(params, hidden, positions, eps):
    """BERT's MLM head on the masked positions: (B, T, w), (B, M) ->
    (B, M, V)."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        h = jax.nn.gelu(h @ params["mlm_w"].T + params["mlm_b"],
                        approximate=False)
        h = _ln(h, params["mlm_ln_g"], params["mlm_ln_b"], eps)
        return h @ params["embed"].T + params["mlm_bias"]


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy of (..., V) logits vs (...) labels."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
