"""Job kind ``train_mlm``: BERT-style masked-LM training through
``SPMDTrainer`` — a fresh seeded host batch every step through the
trainer's normal ``step()``, each step's loss fetched one step later so
the device never waits for the host.

Cell file keys: ``batch``, ``seq_len``, ``masked`` (positions a
sequence), ``mesh``, ``rules``, ``optimizer``, ``optimizer_params``,
``warmup_steps``, ``check_sequences``, ``trace_steps``.
Configuration keys: ``zoo`` ("module:function"), ``zoo_args``,
``zoo_kwargs``, ``train_dtype`` and the ``arch`` group.
"""
import importlib
import time

import numpy as np

from chipbench.harness import flops, reference, trace_reduce

# System (bf16 parameters and activations, flash kernel) against the
# float32 reference, on the MLM logits of the untrained model: max |a - b|
# over max |b|.  bf16 carries 8 mantissa bits (0.4 % a rounding) and the
# forward pass chains some 200 rounded operations; the v5e measured
# 0.027 and 0.029 on two seeds (my chip runs, PR 24), so 0.05 flags a
# wrong or missing operation, not a rounding.  The loss from those
# logits averages the error over 152 masked positions: measured 0.7e-4
# and 1.8e-4 relative, held to 0.5 %.
LOGIT_TOL = 0.05
LOSS_TOL = 0.005


def build_net(config, seed, dtype):
    import mxnet_tpu as mx
    module, fn = config["zoo"].split(":")
    mx.random.seed(seed % (2 ** 31))
    net = getattr(importlib.import_module(module), fn)(
        *config["zoo_args"], **config["zoo_kwargs"])
    net.initialize()
    # finishes the deferred shapes (as bench.py does)
    net(mx.np.zeros((2, 32), dtype="int32"),
        mx.np.zeros((2, 32), dtype="int32"),
        mx.np.full((2,), 32, dtype="int32"),
        mx.np.zeros((2, 4), dtype="int32"))
    if dtype != "float32":
        net.cast(dtype)
    return net


def reference_params(net):
    """BERTModel's parameters in reference.py's layout."""
    def j(p):
        return p.data()._data
    blocks = [{
        "qkv_w": j(l.attn_qkv.weight), "qkv_b": j(l.attn_qkv.bias),
        "out_w": j(l.attn_out.weight), "out_b": j(l.attn_out.bias),
        "ln1_g": j(l.ln1.gamma), "ln1_b": j(l.ln1.beta),
        "f1_w": j(l.ffn1.weight), "f1_b": j(l.ffn1.bias),
        "f2_w": j(l.ffn2.weight), "f2_b": j(l.ffn2.bias),
        "ln2_g": j(l.ln2.gamma), "ln2_b": j(l.ln2.beta),
    } for l in net.encoder.layers._children.values()]
    return {
        "embed": j(net.word_embed.weight),
        "type_embed": j(net.token_type_embed.weight),
        "pos": j(net.encoder.position_weight),
        "emb_ln_g": j(net.encoder.ln.gamma),
        "emb_ln_b": j(net.encoder.ln.beta),
        "mlm_w": j(net.mlm_transform.weight),
        "mlm_b": j(net.mlm_transform.bias),
        "mlm_ln_g": j(net.mlm_ln.gamma), "mlm_ln_b": j(net.mlm_ln.beta),
        "mlm_bias": j(net.mlm_bias),
        "blocks": blocks,
    }


def make_batch(rng, batch, seq_len, masked, vocab):
    """The input layout of bench.py's bench_bert: ids, token types,
    valid lengths, masked positions; labels for the masked positions."""
    x = [rng.integers(0, vocab, (batch, seq_len), dtype=np.int32),
         np.zeros((batch, seq_len), np.int32),
         np.full((batch,), seq_len, np.int32),
         rng.integers(0, seq_len, (batch, masked), dtype=np.int32)]
    y = rng.integers(0, vocab, (batch, masked), dtype=np.int32)
    return x, y


def check_against_reference(net, config, cell, rng):
    """Untrained model, ``check_sequences`` sequences of the cell's
    length: the system's MLM logits and loss against the reference's."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    x, y = make_batch(rng, cell["check_sequences"], cell["seq_len"],
                      cell["masked"], config["arch"]["vocab"])
    got = net(*[mx.np.array(a) for a in x])[-1]._data.astype(jnp.float32)
    num_heads = config["arch"]["heads"]
    eps = config["arch"]["layer_norm_eps"]

    @jax.jit
    def ref_logits(params, ids, segments, positions):
        h = reference.hidden_states(
            params, ids, num_heads=num_heads, causal=False, pre_ln=False,
            eps=eps, gelu_approx=False, segments=segments)
        return reference.mlm_logits(params, h, positions, eps)

    want = ref_logits(reference.to_float32(reference_params(net)),
                      x[0], x[1], x[3])
    logit_err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    loss_got = float(reference.cross_entropy(got, jnp.asarray(y)))
    loss_want = float(reference.cross_entropy(want, jnp.asarray(y)))
    loss_err = abs(loss_got - loss_want) / abs(loss_want)
    return {"logit_err": logit_err, "loss_system": loss_got,
            "loss_reference": loss_want, "loss_err": loss_err,
            "ok": bool(np.isfinite(logit_err) and logit_err <= LOGIT_TOL
                       and loss_err <= LOSS_TOL)}


def run(ctx):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import metrics, parallel

    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    B, T, M = cell["batch"], cell["seq_len"], cell["masked"]
    vocab = config["arch"]["vocab"]
    rng = np.random.default_rng(seed)

    net = build_net(config, seed, config["train_dtype"])
    check = check_against_reference(net, config, cell, rng)

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    trainer = parallel.SPMDTrainer(
        net, lambda logits, labels: loss_fn(logits, labels),
        optimizer=cell["optimizer"],
        optimizer_params=cell["optimizer_params"],
        mesh=parallel.make_mesh(cell["mesh"], devices=ctx["devices"]),
        rules=getattr(parallel, cell["rules"]),
        # the loss reads the MLM logits, the last forward output
        output_transform=lambda out: out[-1])

    def step():
        x, y = make_batch(rng, B, T, M, vocab)
        return trainer.step([mx.np.array(a) for a in x], mx.np.array(y))

    def compiles():
        return int(metrics.COMPILE_MISSES.value
                   + metrics.COMPILE_PERSISTENT_HITS.value)

    t_warm = time.perf_counter()
    losses = [float(step().asnumpy()) for _ in range(cell["warmup_steps"])]
    warmup_s = time.perf_counter() - t_warm
    compiled = int(metrics.COMPILE_MISSES.value)
    loaded = int(metrics.COMPILE_PERSISTENT_HITS.value)

    def pipeline(more, phase=lambda name: None):
        """Steps while ``more()``: step i is dispatched before step
        i - 1's loss is fetched, and the last loss ends the run."""
        pending = None
        while more():
            phase("dispatch")
            t = time.perf_counter()
            loss = step()
            dispatch_s.append(time.perf_counter() - t)
            if pending is not None:
                phase("loss_fetch")
                losses.append(float(pending.asnumpy()))
            pending = loss
        phase("loss_fetch")
        losses.append(float(pending.asnumpy()))

    before = compiles()
    dispatch_s, n_warm = [], len(losses)
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_proc"]
    pipeline(lambda: time.perf_counter() - t0 < ctx["seconds"])
    window_s = time.perf_counter() - t0
    compiled_in_window = compiles() - before
    steps = len(losses) - n_warm

    reduction, breakdown = None, None
    if ctx["trace"]:
        phases, left = [], iter(range(cell["trace_steps"]))
        with trace_reduce.TraceWindow() as tw:
            pipeline(lambda: next(left, None) is not None,
                     lambda name: phases.append(
                         (time.perf_counter_ns(), name)))
        reduction = tw.reduction()
        breakdown = {
            "device_ops": trace_reduce.top(reduction["ops"]),
            "idle_gaps": trace_reduce.gaps_by_phase(
                reduction["gaps"], phases, reduction["offset_ns"]),
        }

    finite = bool(np.all(np.isfinite(losses)))
    arch = config["arch"]
    itemsize = 2 if config["train_dtype"] == "bfloat16" else 4
    flash_flops, flash_bytes = flops.flash_flops_and_bytes(
        B, T, arch["width"], arch["layers"], arch["causal"], itemsize)
    tokens_per_s = steps * B * T / window_s
    return {
        "correct": check["ok"] and finite,
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(losses[n_warm:n_warm + steps]))),
        "compiled_in_window": compiled_in_window,
        "end_to_end": {
            "setup_s": setup_s,
            "train_tokens_per_s_chip": tokens_per_s / ctx["chips"],
        },
        "readings": {
            "warmup_s": warmup_s,
            "dispatch_s": dispatch_s[:steps],
            "traced_steps": cell["trace_steps"],
            "train_flops_per_token": flops.train_flops_per_token(
                arch["layers"], arch["width"], arch["ffn"], T,
                arch["causal"], flops.mlm_head_flops_per_token(
                    arch["width"], arch["vocab"], M, T)),
            "flash_flops_per_step": flash_flops,
            "flash_bytes_per_step": flash_bytes,
        },
        "trace": reduction,
        "breakdown": breakdown,
        "notes": {"check": check, "steps": steps, "window_s": window_s,
                  "first_loss": losses[0], "last_loss": losses[-1],
                  "programs_compiled": compiled, "programs_loaded": loaded,
                  "warmup_s": warmup_s},
    }
