"""The plain reference of the Olmo Hybrid forward pass: float32
``jax.numpy`` under matmul precision "highest", one sequence at a time,
the delta rule as a SEQUENTIAL ``lax.scan`` over positions (one rank-one
update a token, no chunks), dense masked attention, no cache, no
batching, no padding tricks.  ``chipbench/harness/reference_olmo_hybrid.py``
is a copy of this file (``tests/test_olmo_hybrid.py`` holds the two to
identical output).

The equations (allenai ``Olmo-Hybrid-7B`` ``config.json``; ``assumed``
in ``chipbench/configs/olmo_hybrid_7b.json`` lists what it leaves
open).  ``x = E[ids]``; every layer, by its kind::

    linear (Gated DeltaNet, heads of dk keys and dv values):
      [q | k | v] = silu(causal_conv(x W_qkv))          depthwise, no bias
      q = l2norm(q) / sqrt(dk),  k = l2norm(k)          a head, eps 1e-6
      beta = sigmoid(x W_b) (x 2 where negative eigenvalues are allowed)
      alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))
      S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
      o_t = S_t^T q_t
      y = W_out (RMSNorm_head(o; g_o) * silu(x W_g))
    full (heads of d, no position embedding):
      q, k = RMSNorm(x W_q; g_q), RMSNorm(x W_k; g_k)   over the width
      y = W_out concat_h(softmax(q_h k_h^T / sqrt(d) + causal) v_h)
    x = x + RMSNorm(y; g_post_mix);  x = x + RMSNorm(W_down(silu(W_gate x) * W_up x); g_post_ff)
    logits = RMSNorm(x; g_final) H^T

Parameters: the pytree of ``mxnet_tpu.gluon.model_zoo.olmo_hybrid.
_collect`` (``embed``, ``head``, ``lnf_g``, ``layers``: a list of dicts;
Dense weights are (out, in); a linear layer's ``in_w`` rows are q, k, v,
the gate, then a, then b).  ``cfg`` holds ``kinds``, ``num_heads``,
``head_dim``, ``lin_heads``, ``key_dim``, ``value_dim``, ``conv_size``,
``allow_neg_eigval``, ``rms_norm_eps``.
"""
import jax
import jax.numpy as jnp


def to_float32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def gated_deltanet(p, x, cfg, state_dtype=None):
    """x (T, w) -> (y (T, w), the conv's last K - 1 inputs (K - 1,
    conv), the state after the last token (heads, dk, dv)).  With
    ``state_dtype`` the state is rounded to it after every token (a
    control, not the model)."""
    T = x.shape[0]
    H, dk, dv = cfg["lin_heads"], cfg["key_dim"], cfg["value_dim"]
    K, eps = cfg["conv_size"], cfg["rms_norm_eps"]
    nq, nv = H * dk, H * dv
    proj = x @ p["in_w"].T
    qkv = proj[:, :2 * nq + nv]
    gate = proj[:, 2 * nq + nv:2 * nq + 2 * nv]
    a = proj[:, 2 * nq + 2 * nv:2 * nq + 2 * nv + H]
    b = proj[:, 2 * nq + 2 * nv + H:]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])
    conv = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][:, j]
                           for j in range(K)))
    q = l2norm(conv[:, :nq].reshape(T, H, dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2norm(conv[:, nq:2 * nq].reshape(T, H, dk))
    v = conv[:, 2 * nq:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(b)
    if cfg["allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))

    def step(S, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        decayed = a_t[:, None, None] * S
        write = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", decayed, k_t))
        S = decayed + k_t[:, :, None] * write[:, None, :]
        if state_dtype is not None:
            # an op of its own: a pair of converts may be folded away
            finfo = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, finfo.nexp, finfo.nmant)
        return S, jnp.einsum("hde,hd->he", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((H, dk, dv)), (q, k, v, alpha, beta))
    o = rms_norm(o, p["o_norm_g"], eps) * jax.nn.silu(gate.reshape(T, H, dv))
    return o.reshape(T, nv) @ p["out_w"].T, padded[T:], S


def attention(p, x, cfg):
    """x (T, w) -> (y (T, w), k and v (T, heads, d))."""
    T, d, eps = x.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    n = cfg["num_heads"]
    a = n * d
    qkv = x @ p["qkv_w"].T
    q = rms_norm(qkv[:, :a], p["q_norm_g"], eps).reshape(T, n, d)
    k = rms_norm(qkv[:, a:2 * a], p["k_norm_g"], eps).reshape(T, n, d)
    v = qkv[:, 2 * a:].reshape(T, n, d)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, a) @ p["out_w"].T, k, v


def layer(p, x, kind, cfg, state_dtype=None):
    """One layer over x (T, w) float32: (x after it, what it holds: a
    linear layer (conv tail, state), a full layer (k, v))."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        if kind == "linear":
            y, tail, S = gated_deltanet(p, x, cfg, state_dtype)
            held = (tail, S)
        else:
            y, k, v = attention(p, x, cfg)
            held = (k, v)
        x = x + rms_norm(y, p["post_mix_g"], eps)
        gu = x @ p["gate_up_w"].T
        gate, up = gu[:, :gu.shape[1] // 2], gu[:, gu.shape[1] // 2:]
        x = x + rms_norm((jax.nn.silu(gate) * up) @ p["down_w"].T,
                         p["post_ff_g"], eps)
    return x, held


def lm_logits(head, lnf_g, x, eps):
    """The final norm and the untied head (``head`` may be a slice of
    its rows)."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, jnp.asarray(lnf_g, jnp.float32), eps) \
            @ jnp.asarray(head, jnp.float32).T


def forward(params, ids, cfg):
    """(T,) token ids -> ((T, vocab) logits, what each layer holds)."""
    params = to_float32(params)
    x = params["embed"][ids]
    held = []
    for kind, p in zip(cfg["kinds"], params["layers"]):
        x, h = layer(p, x, kind, cfg)
        held.append(h)
    return lm_logits(params["head"], params["lnf_g"], x,
                     cfg["rms_norm_eps"]), held
