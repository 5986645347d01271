"""The plain reference of the Phi-4-mini-flash (SambaY) forward pass:
float32 ``jax.numpy`` under matmul precision "highest", one sequence at
a time, a sequential ``lax.scan`` for the recurrence, dense masked
attention head by head, no cache, no batching, no padding tricks.
``chipbench/harness/reference_phi4flash.py`` is a copy of this file
(``tests/test_phi4flash.py`` holds the two to identical output).

The equations (ISSUE 28; arXiv:2507.06607; ``assumed`` in
``chipbench/configs/phi4_mini_flash.json`` lists what the published
``config.json`` leaves open).  Every layer, for layer index ``i``:

    x = x + mixer_i(LN(x));  x = x + W2 (up * silu(gate)),  [gate, up] = W1 LN(x)

* Mamba-1 (i even, i <= L/2): ``[u, z] = W_in x``; ``u = silu(conv4(u))``
  (causal, depthwise); ``[dt, B, C] = W_x u``; ``dt = softplus(W_dt dt +
  b_dt)``; ``h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t^T`` with ``A =
  -exp(A_log)``; ``m_t = h_t C_t + D u_t``; ``y = W_out (m * silu(z))``.
  Layer L/2 hands ``m`` on as the memory.
* differential attention (i odd, i < L/2: window; i = L/2 + 1: whole
  context): heads in pairs, ``a_j = softmax(q_j k_j^T / sqrt(d) + mask)
  [v_1, v_2]``, ``out = (1 - l0) RMSNorm(a_1 - l a_2)``, ``l =
  exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``.
  Query pair ``n`` reads K/V pair ``n // (query pairs / K/V pairs)``.
* Gated Memory Unit (i even, i > L/2): ``y = W_out (m * silu(W_in x))``.
* cross-attention (i odd, i > L/2 + 1): queries of this layer, K/V of
  layer L/2 + 1.

Parameters: the pytree of ``mxnet_tpu.gluon.model_zoo.phi4flash._collect``
(``embed``, ``lnf_g/b``, ``layers``: a list of dicts; Dense weights are
(out, in)).  ``cfg`` holds ``kinds``, ``units``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``window``, ``d_state``, ``d_conv``,
``dt_rank``, ``layer_norm_eps``.
"""
import jax
import jax.numpy as jnp


def to_float32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def mamba(p, x, cfg):
    """x (T, w) -> (y (T, w), memory m (T, d_inner), state (d_inner, n)
    after the last token)."""
    T, k, r, n = x.shape[0], cfg["d_conv"], cfg["dt_rank"], cfg["d_state"]
    uz = x @ p["in_w"].T
    u, z = uz[:, :uz.shape[1] // 2], uz[:, uz.shape[1] // 2:]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1])), u])
    conv = p["conv_b"] + sum(padded[j:j + T] * p["conv_w"][:, j]
                             for j in range(k))
    u = jax.nn.silu(conv)
    dbc = u @ p["x_w"].T
    dt = jax.nn.softplus(dbc[:, :r] @ p["dt_w"].T + p["dt_b"])
    B, C = dbc[:, r:r + n], dbc[:, r + n:]
    A = -jnp.exp(p["A_log"])

    def step(h, inputs):
        dt_t, u_t, b_t, c_t = inputs
        h = jnp.exp(dt_t[:, None] * A) * h + jnp.outer(dt_t * u_t, b_t)
        return h, h @ c_t + p["D"] * u_t

    state, m = jax.lax.scan(step, jnp.zeros(A.shape), (dt, u, B, C))
    return (m * jax.nn.silu(z)) @ p["out_w"].T, m, state


def differential_attention(p, q, k, v, depth, cfg, window):
    """q (T, w), k and v (T, kv heads * d) -> (T, w).  ``window`` None:
    the whole causal context; else a query sees itself and the
    ``window - 1`` rows before it.  ``depth`` may be traced."""
    T, d = q.shape[0], cfg["head_dim"]
    pairs, kv_pairs = cfg["num_heads"] // 2, cfg["num_kv_heads"] // 2
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (jnp.exp(p["lam_q1"] @ p["lam_k1"])
           - jnp.exp(p["lam_q2"] @ p["lam_k2"]) + l0)
    row, col = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = col <= row
    if window is not None:
        seen = seen & (col > row - window)
    outs = []
    for n in range(pairs):
        m = n // (pairs // kv_pairs)
        vv = v[:, 2 * m * d:(2 * m + 2) * d]                # (T, 2 d)
        a = []
        for j in range(2):
            qj = q[:, (2 * n + j) * d:(2 * n + j + 1) * d]
            kj = k[:, (2 * m + j) * d:(2 * m + j + 1) * d]
            scores = jnp.where(seen, qj @ kj.T / jnp.sqrt(1.0 * d),
                               -jnp.inf)
            a.append(jax.nn.softmax(scores, axis=-1) @ vv)
        diff = a[0] - lam * a[1]
        rms = jnp.sqrt((diff ** 2).mean(-1, keepdims=True)
                       + cfg["layer_norm_eps"])
        outs.append((1.0 - l0) * diff / rms * p["subln_g"])
    return jnp.concatenate(outs, axis=-1) @ p["out_w"].T + p["out_b"]


def layer(p, x, kind, depth, cfg, carry):
    """One layer on float32 parameters ``p``; ``carry`` holds the memory
    (``m``) and the shared K/V rows (``k``, ``v``) once their layers
    have run.  Returns (x, carry, what a cache would hold of the layer:
    a Mamba layer's final state, an attention layer's (k, v), else
    None)."""
    with jax.default_matmul_precision("highest"):
        eps, w = cfg["layer_norm_eps"], cfg["units"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        held = None
        if kind == "mamba":
            y, m, held = mamba(p, h, cfg)
            carry = dict(carry, m=m)
        elif kind == "gmu":
            y = (carry["m"] * jax.nn.silu(h @ p["in_w"].T)) @ p["out_w"].T
        elif kind == "cross":
            y = differential_attention(
                p, h @ p["q_w"].T + p["q_b"], carry["k"], carry["v"],
                depth, cfg, None)
        else:
            qkv = h @ p["qkv_w"].T + p["qkv_b"]
            q, k, v = qkv[:, :w], qkv[:, w:w + kv], qkv[:, w + kv:]
            if kind == "full":
                carry = dict(carry, k=k, v=v)
            y = differential_attention(
                p, q, k, v, depth, cfg,
                cfg["window"] if kind == "window" else None)
            held = (k, v)
        x = x + y
        h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        gate_up = h @ p["mlp_w1"].T
        half = gate_up.shape[1] // 2
        x = x + (gate_up[:, half:] * jax.nn.silu(gate_up[:, :half])) \
            @ p["mlp_w2"].T
        return x, carry, held


def hidden_states(params, ids, cfg):
    """(T,) token ids -> (final hidden states (T, w), per-layer held
    state).  Each layer's parameters are upcast as the layer is reached,
    so bfloat16 weights need float32 room for one layer only when this
    runs outside ``jit``."""
    x = jnp.asarray(params["embed"][ids], jnp.float32)
    carry, held = {}, []
    for depth, (kind, p) in enumerate(zip(cfg["kinds"], params["layers"])):
        x, carry, h = layer(to_float32(p), x, kind, depth, cfg, carry)
        held.append(h)
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, jnp.asarray(params["lnf_g"], jnp.float32),
                       jnp.asarray(params["lnf_b"], jnp.float32),
                       cfg["layer_norm_eps"])
    return x, held


def lm_logits(embed, hidden, chunks=1):
    """Tied head: (..., w) -> (..., V), the vocabulary upcast ``chunks``
    slices at a time."""
    with jax.default_matmul_precision("highest"):
        size = -(-embed.shape[0] // chunks)
        return jnp.concatenate(
            [hidden @ jnp.asarray(embed[i:i + size], jnp.float32).T
             for i in range(0, embed.shape[0], size)], axis=-1)


def forward(params, ids, cfg):
    """(T,) token ids -> (T, V) logits."""
    hidden, _ = hidden_states(params, ids, cfg)
    return lm_logits(params["embed"], hidden)
