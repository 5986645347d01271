"""The plain reference of the Ouro (LoopLM) forward pass: float32
``jax.numpy`` under matmul precision "highest", one sequence at a time,
dense masked attention head by head, two Python loops (loop steps
around layers), no cache, no kernel, no batching, no ``scan``.
``chipbench/harness/reference_ouro.py`` is a copy of this file
(``tests/test_ouro.py`` holds the two to identical bytes).

The equations (ISSUE 35; ``assumed`` in
``chipbench/configs/ouro_2_6b.json`` lists what the published
``config.json`` leaves open).  ``x`` is ``(T, w)``, row ``n`` at
position ``n``, the same in every loop step::

    x = E[ids]
    for t in 0 .. loop_steps - 1:          # the SAME layers every time
      for l in 0 .. layers - 1:
        h = RMSNorm(x; g1_l)               # x rsqrt(mean(x^2) + eps) g
        q, k, v = h Wq_l, h Wk_l, h Wv_l   # (T, heads, d); no biases
        q, k = rope(q), rope(k)            # pairs (j, j + d/2), all d
        a = softmax(q k^T / sqrt(d) + causal) v;  a = concat(a) Wo_l
        x = x + RMSNorm(a; g2_l)           # the branch's OUTPUT is normed
        h = RMSNorm(x; g3_l)
        m = Wdown_l (silu(Wgate_l h) * Wup_l h)
        x = x + RMSNorm(m; g4_l)
      x = RMSNorm(x; g_final)              # after EVERY loop step
      z_t = x;  lam_t = sigmoid(z_t w_gate + b_gate)
    p_t = lam_t prod_{u<t} (1 - lam_u)  (t < last);  p_last = what is left
    exit step = the first t whose cumulative p reaches the threshold;
                at the published threshold of 1: the last, always
    logits = z_exit H^T                    # untied head

Departure from the published code, noted: none in the mathematics; the
exit rule at threshold 1 is read as "every token makes every step".

Parameters: the pytree of ``mxnet_tpu.gluon.model_zoo.ouro._collect``
(``embed``, ``head``, ``lnf_g``, ``gate_w`` (1, w), ``gate_b`` (1,),
``layers``: a dict of arrays STACKED on a leading layer axis).  Dense
weights are (out, in): ``qkv_w`` (layers, 3 heads d, w) stacks Wq, Wk,
Wv along out; ``out_w``; ``gate_up_w`` (layers, 2 f, w) Wgate then Wup;
``down_w``; ``norm_g`` (layers, 4, w) is g1 .. g4.  ``cfg`` holds
``num_layers``, ``num_heads``, ``head_dim``, ``loop_steps``,
``exit_threshold``, ``rope_theta``, ``rms_norm_eps``.
"""
import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (T, heads, d), row t at position t: each pair (j, j + d/2)
    turns by t * theta^(-2j / d)."""
    T, _, d = x.shape
    angle = jnp.arange(T)[:, None] * theta ** (-jnp.arange(0, d, 2) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, h, cfg):
    """h (T, w) -> (a Wo (T, w), k (T, heads, d) as a cache would hold
    it, v)."""
    T = h.shape[0]
    d, n = cfg["head_dim"], cfg["num_heads"]
    w = f32(p["qkv_w"])
    q = (h @ w[:n * d].T).reshape(T, n, d)
    k = (h @ w[n * d:2 * n * d].T).reshape(T, n, d)
    v = (h @ w[2 * n * d:].T).reshape(T, n, d)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(i):
        scores = jnp.where(seen, q[:, i] @ k[:, i].T / jnp.sqrt(1.0 * d),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, i]

    # one head at a time: (T, T) scores are all that is live
    a = jax.lax.map(head, jnp.arange(n))                    # (n, T, d)
    return a.transpose(1, 0, 2).reshape(T, n * d) @ f32(p["out_w"]).T, k, v


def mlp(p, h):
    gate_up = h @ f32(p["gate_up_w"]).T
    f = gate_up.shape[1] // 2
    return (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]) \
        @ f32(p["down_w"]).T


def layer_of(params, l):
    """Layer ``l``'s weights out of the stacked ones."""
    return {name: a[l] for name, a in params["layers"].items()}


def layer(p, x, cfg):
    """One pass of one layer on its weights ``p`` (upcast a matrix at a
    time).  Returns (x, k, v), k and v as a cache would hold them."""
    eps = cfg["rms_norm_eps"]
    g = f32(p["norm_g"])
    with jax.default_matmul_precision("highest"):
        a, k, v = attention(p, rms_norm(x, g[0], eps), cfg)
        x = x + rms_norm(a, g[1], eps)
        x = x + rms_norm(mlp(p, rms_norm(x, g[2], eps)), g[3], eps)
        return x, k, v


def loop_end(params, x, cfg):
    """The final norm that closes a loop step: ``z_t``, and the next
    step's input."""
    return rms_norm(x, f32(params["lnf_g"]), cfg["rms_norm_eps"])


def hidden_states(params, ids, cfg):
    """(T,) token ids -> (every step's z (loop_steps, T, w), every
    pass's (k, v): ``held[t][l]``)."""
    x = f32(params["embed"])[ids]
    z, held = [], []
    for _ in range(cfg["loop_steps"]):
        held.append([])
        for l in range(cfg["num_layers"]):
            x, k, v = layer(layer_of(params, l), x, cfg)
            held[-1].append((k, v))
        x = loop_end(params, x, cfg)
        z.append(x)
    return jnp.stack(z), held


def exit_probabilities(params, z):
    """z (loop_steps, T, w) -> (T, loop_steps): the chance that a token
    leaves after each step."""
    with jax.default_matmul_precision("highest"):
        lam = jax.nn.sigmoid(z @ f32(params["gate_w"])[0]
                             + f32(params["gate_b"])[0])
    probs, stay = [], jnp.ones_like(lam[0])
    for t in range(len(lam) - 1):
        probs.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(probs + [stay], axis=-1)


def exit_step(probs, threshold):
    """The step each token's logits are read at, (T,)."""
    last = probs.shape[-1] - 1
    if threshold >= 1.0:
        return jnp.full(probs.shape[:-1], last)
    reached = jnp.cumsum(probs, axis=-1) >= threshold
    return jnp.where(reached.any(-1), reached.argmax(-1), last)


def lm_logits(head, hidden):
    """Untied head: (..., w) -> (..., vocab)."""
    with jax.default_matmul_precision("highest"):
        return hidden @ f32(head).T


def forward(params, ids, cfg):
    """(T,) token ids -> (logits (T, vocab), exit probabilities (T,
    loop_steps))."""
    z, _ = hidden_states(params, ids, cfg)
    probs = exit_probabilities(params, z)
    at = exit_step(probs, cfg["exit_threshold"])
    hidden = z[at, jnp.arange(z.shape[1])]
    return lm_logits(params["head"], hidden), probs
