"""The plain reference of the Command A+ (``cohere2_moe``) forward pass:
float32 ``jax.numpy`` under matmul precision "highest", one sequence at
a time, dense masked attention head by head, a loop over the experts,
no cache, no kernel, no batching.
``chipbench/harness/reference_cohere2moe.py`` is a copy of this file
(``tests/test_cohere2moe.py`` holds the two to identical bytes).

The equations (ISSUE 32; ``assumed`` in
``chipbench/configs/command_a_plus.json`` lists what the published
``config.json`` leaves open).  ``x`` is ``(T, w)``; layer ``i`` is a
window layer unless it is the last of its period of four::

    h = LayerNorm(x)                    # mean-centred, gain only
    q = h Wq (T, heads, d);  k = h Wk,  v = h Wv (T, kv heads, d)
    window layer:  q, k = rope(q), rope(k)    # pairs (2j, 2j+1), all d
    full layer:    no position embedding
    a = softmax(q k^T / sqrt(d) + mask) v;  query head n reads K/V head
        n // (heads / kv heads);  mask: causal, and on a window layer
        key j is seen by query t iff 0 <= t - j < window;  a = a Wo
    s = sigmoid(h Wr);  I = top-k of s;  w_e = s_e / sum_{e' in I} s_e'
    routed = sum_{e in I, e held} w_e Wdown_e (silu(Wgate_e h) * Wup_e h)
    shared = (1 / n) sum_j Wdown'_j (silu(Wgate'_j h) * Wup'_j h)
    y = x + a + routed + shared
    logits = logit_scale * LayerNorm(x_L) E^T        # E: the rows held

A model holds the experts ``experts_held = (lo, hi)`` and the first
``vocab_rows`` rows of the tied embedding: the router scores all the
experts, and what an absent expert would have added is left out, here as
in the program.

Parameters: the pytree of ``mxnet_tpu.gluon.model_zoo.cohere2moe._collect``
(``embed``, ``lnf_g``, ``layers``: a list of dicts).  Dense weights are
(out, in): ``qkv_w`` stacks Wq, Wk, Wv along out; ``out_w``;
``router_w``.  The experts' are stacked (expert, in, out): ``expert_in``
(held, w, 2 f) holds Wgate then Wup along out, ``expert_out`` (held, f,
w) Wdown; ``shared_in`` / ``shared_out`` alike.  ``cfg`` holds
``kinds``, ``num_heads``, ``num_kv_heads``, ``head_dim``, ``window``,
``rope_theta``, ``top_k``, ``experts_held``, ``layer_norm_eps``,
``logit_scale``.
"""
import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a, jnp.float32)


def layer_norm(x, g, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g


def rope(x, theta):
    """x (T, heads, d), row t at position t: each pair (2j, 2j+1) turns
    by t * theta^(-2j / d)."""
    T, _, d = x.shape
    angle = jnp.arange(T)[:, None] * theta ** (-jnp.arange(0, d, 2) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(p, h, kind, cfg):
    """h (T, w) -> (a Wo (T, w), k (T, kv heads, d) as a cache would
    hold it, v)."""
    T = h.shape[0]
    d, nq, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    w = f32(p["qkv_w"])
    q = (h @ w[:nq * d].T).reshape(T, nq, d)
    k = (h @ w[nq * d:(nq + nkv) * d].T).reshape(T, nkv, d)
    v = (h @ w[(nq + nkv) * d:].T).reshape(T, nkv, d)
    row, col = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = col <= row
    if kind == "window":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
        seen = seen & (row - col < cfg["window"])

    def head(n):
        m = n // (nq // nkv)
        scores = jnp.where(seen, q[:, n] @ k[:, m].T / jnp.sqrt(1.0 * d),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, m]

    # one head at a time: (T, T) scores are all that is live
    a = jax.lax.map(head, jnp.arange(nq))                   # (nq, T, d)
    return a.transpose(1, 0, 2).reshape(T, nq * d) @ f32(p["out_w"]).T, k, v


def expert(h, w_in, w_out):
    """One gated FFN: Wdown (silu(Wgate h) * Wup h)."""
    gate_up = h @ f32(w_in)
    f = gate_up.shape[1] // 2
    return (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]) @ f32(w_out)


def router(p, h, cfg):
    """(scores (T, experts), the chosen experts (T, k), their weights)."""
    scores = jax.nn.sigmoid(h @ f32(p["router_w"]).T)
    top, chosen = jax.lax.top_k(scores, cfg["top_k"])
    return scores, chosen, top / top.sum(-1, keepdims=True)


def routed(p, h, chosen, weights, cfg):
    """The held experts' part of the routed sum, an expert at a time."""
    lo, hi = cfg["experts_held"]
    out = jnp.zeros_like(h)
    for e in range(lo, hi):
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        out = out + w_e[:, None] * expert(h, p["expert_in"][e - lo],
                                          p["expert_out"][e - lo])
    return out


def shared(p, h):
    n = p["shared_in"].shape[0]
    return sum(expert(h, p["shared_in"][j], p["shared_out"][j])
               for j in range(n)) / n


def layer(p, x, kind, cfg):
    """One layer on parameters ``p`` (upcast a matrix at a time).
    Returns (y, (k, v) as a cache would hold them, the router's scores
    (T, experts))."""
    with jax.default_matmul_precision("highest"):
        h = layer_norm(x, f32(p["ln_g"]), cfg["layer_norm_eps"])
        a, k, v = attention(p, h, kind, cfg)
        scores, chosen, weights = router(p, h, cfg)
        y = x + a + routed(p, h, chosen, weights, cfg) + shared(p, h)
        return y, (k, v), scores


def hidden_states(params, ids, cfg):
    """(T,) token ids -> (final hidden states (T, w), each layer's (k,
    v), each layer's router scores)."""
    x = f32(params["embed"][ids])
    held, scores = [], []
    for kind, p in zip(cfg["kinds"], params["layers"]):
        x, kv, s = layer(p, x, kind, cfg)
        held.append(kv)
        scores.append(s)
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, f32(params["lnf_g"]), cfg["layer_norm_eps"])
    return x, held, scores


def lm_logits(embed, hidden, cfg):
    """Tied head over the rows held: (..., w) -> (..., vocab_rows)."""
    with jax.default_matmul_precision("highest"):
        return cfg["logit_scale"] * (hidden @ f32(embed).T)


def forward(params, ids, cfg):
    """(T,) token ids -> (T, vocab_rows) logits."""
    hidden, _, _ = hidden_states(params, ids, cfg)
    return lm_logits(params["embed"], hidden, cfg)
