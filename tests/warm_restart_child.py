"""Child process of tests/test_warm_restart.py: run one group of the
repo's compiled programs once each and print, per program, what this
boot compiled (``mxnet_compile_misses_total``), what it loaded from
jax's persistent cache (``mxnet_compile_persistent_hits_total``) and a
SHA-256 of the program's outputs.  The parent test runs it twice
against one ``JAX_COMPILATION_CACHE_DIR`` and compares the two reports.

    python tests/warm_restart_child.py <trainer|served|gpt|hybrid> WORKDIR
"""
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp  # noqa: E402
import jax  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import metrics  # noqa: E402

REPORT = {}


def _digest(out) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(out):
        a = onp.asarray(leaf)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(onp.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def probe(name, fn):
    """Run ``fn`` (inputs already on the device), wait for what it
    returns, and book the counters' movement to ``name``."""
    c0 = metrics.COMPILE_MISSES.value
    l0 = metrics.COMPILE_PERSISTENT_HITS.value
    out = fn()
    jax.block_until_ready(out)
    REPORT[name] = {
        "compiled": int(metrics.COMPILE_MISSES.value - c0),
        "loaded": int(metrics.COMPILE_PERSISTENT_HITS.value - l0),
        "digest": _digest(out)}
    return out


# ---------------------------------------------------------------------------
# the trainer: spmd.step, spmd.step with donated inputs, run_steps' fused
# program, an un-recorded bulk segment
# ---------------------------------------------------------------------------

def group_trainer(work):
    from mxnet_tpu import bulk
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {"learning_rate": 0.05},
                          mesh=make_mesh({"dp": 1},
                                         devices=jax.devices()[:1]))

    def batch(s, lead=()):
        rng = onp.random.RandomState(100 + s)
        return (mx.np.array(rng.uniform(-1, 1, lead + (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, lead + (8, 4)).astype("f4")))

    def steps(first):
        # two steps: the second replays the compiled program
        return [trainer.step(*batch(first + i))._data for i in range(2)]

    probe("spmd.step", lambda: steps(0))
    trainer._set_input_donation(True)        # what the prefetched fit does
    probe("spmd.step_donated_inputs", lambda: steps(2))
    trainer._set_input_donation(False)
    probe("spmd.multi", lambda: trainer.run_steps(*batch(4, (3,)))._data)

    prev = bulk.set_max_ops(16)
    try:
        a = mx.np.array(onp.arange(8, dtype="float32"))
        a.asnumpy()
        # no autograd.record(): the segment is an un-recorded one
        probe("bulk.segment",
              lambda: ((a * 2.0 + 1.0).tanh() * a).asnumpy())
    finally:
        bulk.flush_all("waitall")
        bulk.set_max_ops(prev)


# ---------------------------------------------------------------------------
# ServedModel over an export's bucket grid
# ---------------------------------------------------------------------------

def group_served(work):
    from mxnet_tpu import serving

    prefix = os.path.join(work, "m")
    if not os.path.exists(prefix + "-symbol.json"):
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(8, activation="relu"),
                mx.gluon.nn.Dense(3))
        net.initialize()
        net.hybridize()
        net(mx.np.zeros((2, 6), dtype="float32"))
        net.export(prefix, dynamic_batch=True)
    model = serving.load_served(prefix)
    rng = onp.random.RandomState(7)
    for b in (1, 2, 4):
        x = rng.uniform(-1, 1, (b, 6)).astype("f4")
        probe(f"served.batch{b}", lambda: model.predict([x]))


# ---------------------------------------------------------------------------
# the generation engines
# ---------------------------------------------------------------------------

def _serve(eng, prompts, n):
    """Warm the engine's whole grid up, serve ``prompts`` greedily and
    return the token streams."""
    eng.warmup()
    streams = [eng.submit(p, max_new_tokens=n) for p in prompts]
    for _ in range(400):
        if all(s.finished for s in streams):
            break
        eng.run_iteration()
    assert all(s.finished for s in streams), "engine did not finish"
    return [onp.asarray(s.tokens, "int32") for s in streams]


def _rows(cache):
    return [onp.asarray(b) for b in cache._k + cache._v]


def group_gpt(work):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import DecodeModel, GenerationEngine
    from mxnet_tpu.serving.kv_cache import _shrink_rows

    mx.random.seed(0)
    net = GPTModel(vocab_size=97, num_layers=2, units=32, hidden_size=48,
                   num_heads=4, max_length=64, dropout=0.0)
    net.initialize(mx.init.Normal(1.0))
    net(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(net), max_slots=2,
                           kv_buckets=(16, 32), max_tokens=24,
                           prefix_slots=2, spec_mode="self", spec_k=2)
    model, S = eng.model, eng.max_slots
    # a cache of the engine's own shapes: what is compiled for it is
    # what the engine runs
    cache = model.make_cache(S, eng.grid, prefix_slots=0)
    pb = int(eng.prompt_buckets[0])
    prompt = onp.arange(3, 3 + pb - 2, dtype="int32") % 97
    t0 = prompt.shape[0]

    logits, ks, vs = probe("gpt.prefill", lambda: model.prefill(prompt, pb))
    probe("gpt.select", lambda: onp.int32(model.select(
        logits, seed=0, counter=0, temperature=1.0, top_k=1, top_p=1.0,
        method=0)))
    probe("gpt.prefill_suffix", lambda: model.prefill_suffix(
        prompt[:3], ks, vs, q=pb, bucket_len=pb))
    pb2 = int(eng.prompt_buckets[1])
    big = model.prefill(onp.arange(pb2, dtype="int32") % 97, pb2)
    probe("gpt.shrink_rows", lambda: _shrink_rows(
        list(big[1]) + list(big[2]), pb))

    slot = cache.alloc()

    def write():
        cache.write_prompt(slot, ks, vs, t0)
        return _rows(cache)

    probe("gpt.row_write", write)
    toks = onp.zeros((S,), "int32")
    toks[slot] = int(logits.argmax())
    pos = onp.zeros((S,), "int32")
    pos[slot] = t0

    def step():
        return model.step(cache, toks, pos), _rows(cache)

    probe("gpt.step_donated", step)
    drafts = probe("gpt.self_draft",
                   lambda: eng._draft.propose(cache, toks, pos))
    cand = onp.concatenate([toks[:, None],
                            onp.asarray(drafts, "int32")], axis=1)

    def verify():
        return model.verify(cache, cand, pos), _rows(cache)

    probe("gpt.verify_donated", verify)

    def grow():
        cache.grow(int(eng.grid[1]))
        return _rows(cache)

    probe("gpt.grow_rows", grow)

    # the whole engine: two prompts that share a bucket-aligned prefix
    # (the second admission takes the suffix path), drafted and verified
    shared = list(range(5, 5 + pb))
    probe("gpt.engine", lambda: _serve(
        eng, [onp.array(shared + [9, 4], "int32"),
              onp.array(shared + [7], "int32")], 12))


def group_hybrid(work):
    from mxnet_tpu.gluon.model_zoo.phi4flash import get_phi4flash
    from mxnet_tpu.serving import DecodeModel, GenerationEngine

    mx.random.seed(0)
    net = get_phi4flash("tiny", dtype="float32")
    net.initialize()
    net.collect_params().setattr("grad_req", "null")
    eng = GenerationEngine(DecodeModel.from_block(net), max_slots=2,
                           kv_buckets=(64,), max_tokens=24,
                           prefix_slots=0)
    model, S = eng.model, eng.max_slots
    cache = model.make_cache(S, eng.grid, prefix_slots=0)
    pb = int(eng.prompt_buckets[0])
    prompt = onp.arange(3, 3 + pb - 5, dtype="int32") % 503
    t0 = prompt.shape[0]

    logits, ks, vs, state = probe("hybrid.prefill",
                                  lambda: model.prefill(prompt, pb))
    slot = cache.alloc()

    def write():
        # rows by the un-donated write, then state, conv tail and
        # window rings by the donated install
        cache.write_prompt(slot, ks, vs, t0, state=state)
        return _rows(cache), cache.state

    probe("hybrid.row_write_and_state_install_donated", write)
    toks = onp.zeros((S,), "int32")
    toks[slot] = int(logits.argmax())
    pos = onp.zeros((S,), "int32")
    pos[slot] = t0

    def step():
        return model.step(cache, toks, pos), _rows(cache), cache.state

    probe("hybrid.step_donated", step)
    probe("hybrid.engine", lambda: _serve(
        eng, [onp.arange(7, 27, dtype="int32"),
              onp.arange(40, 49, dtype="int32")], 10))


GROUPS = {"trainer": group_trainer, "served": group_served,
          "gpt": group_gpt, "hybrid": group_hybrid}


if __name__ == "__main__":
    GROUPS[sys.argv[1]](sys.argv[2])
    print(json.dumps({
        "programs": REPORT,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "total": {"compiled": int(metrics.COMPILE_MISSES.value),
                  "loaded": int(metrics.COMPILE_PERSISTENT_HITS.value)}}))
