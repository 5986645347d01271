"""The Ouro (LoopLM) family: the zoo model, its serving path
(``serving.loop`` over a ``PagedKVCache`` of stacked rows, one entry a
(loop step, layer)) at a CPU size that keeps a stack and a loop both
deeper than 2 (3 layers x 3 loop steps, 4 heads of 16), against the
plain reference in ``tests/reference_ouro.py``."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics, serving, tracing
from mxnet_tpu.gluon.model_zoo import cohere2moe as c2
from mxnet_tpu.gluon.model_zoo import ouro
from mxnet_tpu.serving import kv_cache, loop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import reference_ouro as ref                                  # noqa: E402
from decode_families import tiny_gpt, tiny_ouro               # noqa: E402
from serial_decode import (StepCounters, check_log,           # noqa: E402
                           run_staggered, serial_transcript)

VOCAB, LAYERS, STEPS, HEADS, D = 512, 3, 3, 4, 16
ENTRIES, C = STEPS * LAYERS, HEADS * D
TOL = 2e-5      # float32 system against the float32 reference


@pytest.fixture(scope="module")
def net():
    return tiny_ouro()


@pytest.fixture(scope="module")
def params(net):
    return ouro._collect(net)


@pytest.fixture(scope="module")
def model(net):
    return serving.DecodeModel.from_block(net)


def new_engine(model, **kw):
    kw = dict(dict(max_slots=4, kv_buckets=(64, 128), prefix_slots=0,
                   max_tokens=64), **kw)
    return serving.GenerationEngine(model, **kw)


def run_all(engine, *streams):
    while engine.run_iteration():
        pass
    return [s.result() for s in streams]


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n, dtype=np.int32)


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---------------------------------------------------------------------------
# the zoo model
# ---------------------------------------------------------------------------

def test_the_tiny_size_keeps_a_stack_and_a_loop(net):
    cfg = net.config
    assert (cfg["num_layers"], cfg["loop_steps"]) == (LAYERS, STEPS)
    assert cfg["exit_threshold"] == 1.0
    # the layers' weights are declared stacked on a layer axis
    assert net.qkv_w.shape == (LAYERS, 3 * C, 64)
    assert net.norm_g.shape == (LAYERS, 4, 64)
    assert net.head_weight.shape == net.word_embed_weight.shape


def test_parameter_count_from_the_declared_shapes():
    big = ouro.get_ouro("ouro_2_6b", dtype="bfloat16")
    # shapes only: nothing is initialised
    assert all(p._data is None for p in big.collect_params().values())
    assert big.num_parameters() == 2_667_974_657
    layer = sum(int(np.prod(p.shape[1:]))
                for name, p in big.collect_params().items()
                if name in ouro._LAYER_SHAPES)
    assert layer == 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 \
        == 51_388_416
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro_2_6b.json")) as f:
        config = json.load(f)
    assert "2,667,974,657" in config["parameters"]
    assert config["reduced"] == []
    arch = config["arch"]
    assert (arch["layers"], arch["loop_steps"], arch["width"],
            arch["heads"], arch["head_dim"], arch["ffn"], arch["vocab"]) \
        == tuple(big.config[k] for k in (
            "num_layers", "loop_steps", "units", "num_heads", "head_dim",
            "hidden_size", "vocab_size"))


def test_bad_specs_are_refused():
    with pytest.raises(ValueError, match="unknown ouro spec"):
        ouro.get_ouro("nope")
    with pytest.raises(ValueError, match="loop steps"):
        ouro.get_ouro("tiny", loop_steps=0)


@pytest.mark.parametrize("what", ["logits", "every_z", "exit_probabilities"])
def test_forward_matches_the_reference(net, params, what):
    toks = np.stack([prompt(21, 1), prompt(21, 2)])
    cfg = net.config
    if what == "every_z":
        for t in toks:
            z, k, v = ouro.forward_sequence(params, jnp.asarray(t), cfg)
            want, held = ref.hidden_states(params, t, cfg)
            assert z.shape == (STEPS, 21, 64) and rel(z, want) < TOL
            assert k.shape == (STEPS, LAYERS, 21, HEADS, D)
            assert max(rel(k[s, l], held[s][l][0])
                       for s in range(STEPS) for l in range(LAYERS)) < TOL
            assert max(rel(v[s, l], held[s][l][1])
                       for s in range(STEPS) for l in range(LAYERS)) < TOL
        return
    logits, probs = net(mx.np.array(toks))
    want = [ref.forward(params, t, cfg) for t in toks]
    if what == "logits":
        got = logits.asnumpy()
        assert got.dtype == np.float32 and got.shape == (2, 21, VOCAB)
        assert rel(got, np.stack([w[0] for w in want])) < TOL
    else:
        got = probs.asnumpy()
        assert got.shape == (2, 21, STEPS)
        np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-5)
        # a gate loud enough that the steps' shares differ
        assert got[..., :-1].max() > 0.2
        assert np.abs(got - np.stack([w[1] for w in want])).max() < TOL


def test_forward_is_differentiable(net):
    w = net.qkv_w.data()
    w.attach_grad()
    with mx.autograd.record():
        loss = net(mx.np.array(prompt(9)[None]))[0].sum()
    loss.backward()
    # every layer of the stack got a gradient through every loop step
    assert (np.abs(w.grad.asnumpy()).max(axis=(1, 2)) > 0).all()
    w.attach_grad("null")


def test_the_two_copies_of_the_reference_agree(params, net):
    with open(os.path.join(ROOT, "tests", "reference_ouro.py")) as a, \
            open(os.path.join(ROOT, "chipbench", "harness",
                              "reference_ouro.py")) as b:
        assert a.read() == b.read()
    from chipbench.harness import reference_ouro as copy
    ids = prompt(19, 3)
    for got, want in zip(copy.forward(params, ids, net.config),
                         ref.forward(params, ids, net.config)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rope_pairs_the_two_halves_of_a_head():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, HEADS, D))
    pos = jnp.arange(6)
    got = ouro.rope(x, pos, 1e6)
    assert rel(got, ref.rope(x, 1e6)) < 1e-5
    # a rotation of the pairs (j, j + d / 2): their norms kept, position
    # 0 untouched; and not the interleaved pairing
    pairs = lambda a: jnp.stack([a[..., :D // 2], a[..., D // 2:]], -1)
    assert rel(jnp.linalg.norm(pairs(got), axis=-1),
               jnp.linalg.norm(pairs(x), axis=-1)) < 1e-5
    assert rel(got[0], x[0]) < 1e-6
    assert rel(c2.rope(x, pos, 1e6), got) > 0.1
    assert rel(c2.rope(x, pos, 1e6, pairing="half"), got) == 0
    with pytest.raises(ValueError, match="pairing"):
        c2.rope(x, pos, 1e6, pairing="thirds")


def test_the_exit_rule_by_hand():
    probs = jnp.asarray([[0.1, 0.2, 0.7], [0.6, 0.3, 0.1], [1.0, 0.0, 0.0]])
    # the published threshold: the last step, whatever the gates say
    np.testing.assert_array_equal(ouro.exit_step(probs, 1.0), [2, 2, 2])
    np.testing.assert_array_equal(ouro.exit_step(probs, 0.5), [2, 0, 0])
    np.testing.assert_array_equal(ouro.exit_step(probs, 0.25), [1, 0, 0])
    np.testing.assert_array_equal(ref.exit_step(probs, 0.25), [1, 0, 0])
    np.testing.assert_array_equal(ref.exit_step(probs, 1.0), [2, 2, 2])


# ---------------------------------------------------------------------------
# through the cache: prefill, then the decode program, against the
# reference's full forward pass
# ---------------------------------------------------------------------------

def decode_through_the_cache(model, prompts, steps, buckets=(1024,),
                             seed=9):
    """Admit ``prompts`` (one a slot; None leaves the slot free) into a
    fresh cache, drive ``steps`` forced tokens through ``model.step``.
    Returns every sequence (prompt + forced), the step's answers
    (steps, slots) and the cache."""
    S = len(prompts)
    cache = model.make_cache(S, buckets)
    grid = (64, 128, 256, 512, 1024)
    for slot, p in enumerate(prompts):
        if p is None:
            continue
        _, ks, vs = model.prefill(p, min(b for b in grid if b >= len(p)))
        cache.write_prompt(slot, ks, vs, len(p))
    forced = np.random.default_rng(seed).integers(
        0, VOCAB, (steps, S), dtype=np.int32)
    answers = []
    for toks in forced:
        cache.ensure_capacity(cache.needed_capacity())
        answers.append(model.step(cache, toks,
                                  np.maximum(cache.positions, 0)))
        cache.positions[cache.positions >= 0] += 1
    seqs = [None if p is None else np.concatenate([p, forced[:, s]])
            for s, p in enumerate(prompts)]
    return seqs, np.stack(answers), cache


def worst_against_the_reference(model, params, seqs, prompts, answers,
                                cache):
    """(the worst relative error of any entry's K or V rows a live slot
    holds, decode tokens that differ from the reference's argmax where
    its two largest logits are not within rounding)."""
    cfg, worst, moved = model.cfg, 0.0, 0
    for slot, seq in enumerate(seqs):
        if seq is None:
            continue
        n, t0 = len(seq), len(prompts[slot])
        z, held = ref.hidden_states(params, seq, cfg)
        for t in range(STEPS):
            for l in range(LAYERS):
                e = t * LAYERS + l
                for got, want in zip((cache.k(e), cache.v(e)), held[t][l]):
                    worst = max(worst, rel(
                        np.asarray(got[slot]).T[:n],
                        np.asarray(want).reshape(n, -1)))
        logits = np.asarray(ref.lm_logits(params["head"], z[-1][t0:]))
        top2 = np.sort(logits, axis=-1)[:, -2:]
        firm = top2[:, 1] - top2[:, 0] > 1e-4 * np.abs(logits).max()
        moved += int((answers[:, slot] != logits.argmax(-1))[firm].sum())
        assert firm.sum() >= len(firm) - 1
    return worst, moved


def test_prefill_then_decode_alone_equals_the_full_forward(model, params):
    prompts = [prompt(13, 20)]
    seqs, answers, cache = decode_through_the_cache(model, prompts, 8)
    assert worst_against_the_reference(
        model, params, seqs, prompts, answers, cache) == (
            pytest.approx(0, abs=TOL), 0)


def test_a_batch_at_different_positions_across_a_block_boundary(model,
                                                                params):
    """Four slots: a short one, a free one that rides along, one that
    starts past the kernel's first 512-position block and one that
    CROSSES it during the steps; every entry of every live slot is the
    reference's."""
    prompts = [prompt(7, 21), None, prompt(530, 22), prompt(507, 23)]
    seqs, answers, cache = decode_through_the_cache(model, prompts, 10)
    assert cache.bucket == 1024 and list(cache.positions) == [
        17, -1, 540, 517]
    worst, moved = worst_against_the_reference(
        model, params, seqs, prompts, answers, cache)
    assert worst < TOL and moved == 0


def test_slots_on_either_side_of_an_edge_of_the_walks_block(model, params):
    """The one call a pass walks blocks of 128 positions of the 1024
    bucket: a slot whose column lands in the last lane of a block, one
    in the first lane of the next, one that crosses the edge during the
    steps and one that crosses the next edge; every entry of every slot
    is the reference's, the tile that took the column included."""
    from mxnet_tpu.ops.pallas import decode_attention as da
    assert da.append_block(1024) == 128
    prompts = [prompt(127, 40), prompt(128, 41), prompt(122, 42),
               prompt(250, 43)]
    seqs, answers, cache = decode_through_the_cache(model, prompts, 10)
    assert cache.bucket == 1024 and list(cache.positions) == [
        137, 138, 132, 260]
    worst, moved = worst_against_the_reference(
        model, params, seqs, prompts, answers, cache)
    assert worst < TOL and moved == 0


def test_the_rows_grow_through_the_bucket_grid(model, params):
    prompts = [prompt(60, 24), prompt(5, 25)]
    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    seqs, answers, cache = decode_through_the_cache(
        model, prompts, 8, buckets=(64, 128))
    assert cache.bucket == 128
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 1
    worst, moved = worst_against_the_reference(
        model, params, seqs, prompts, answers, cache)
    assert worst < TOL and moved == 0


@pytest.mark.parametrize("n", [5, 64])
def test_padded_prefill_equals_unpadded_prefill(model, n):
    p = prompt(n, 30 + n)
    padded = model._prefill_fn(
        model.params, jnp.asarray(np.pad(p, (0, 128 - n))), np.int32(n))
    exact = model._prefill_fn(model.params, jnp.asarray(p), np.int32(n))
    assert rel(padded[0], exact[0]) < TOL
    for a, b in zip(padded[1] + padded[2], exact[1] + exact[2]):
        assert a.shape == (ENTRIES, 128, HEADS, D)
        assert rel(np.asarray(a)[:, :n], np.asarray(b)) < TOL


# ---------------------------------------------------------------------------
# the programs are loops, and nothing the size of an entry is copied
# ---------------------------------------------------------------------------

def _step_jaxpr(model, S=3, L=128):
    rows = [jax.ShapeDtypeStruct((ENTRIES, S, C, L), jnp.float32)]
    i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((S,), jnp.float32)
    return str(jax.make_jaxpr(model._step_fn)(
        model.params, rows, rows, i32, i32, i32, i32, f32, i32, f32, i32))


def test_the_decode_program_is_a_loop_over_the_stacked_cache(model):
    """One loop over the loop steps around one over the layers (both
    ``scan`` in the jaxpr, ``while`` in the HLO), with the ONE kernel
    that writes the column and reads the rows called once inside (not 9
    unrolled, and no write beside it), and no value of one entry's
    shape anywhere: the kernel takes the whole stack and the entry's
    index."""
    text = _step_jaxpr(model)
    assert text.count("pallas_call[") == 1 and text.count("scan[") == 2
    assert "name=ragged_attention" in text
    assert "f32[9,3,64,128]" in text and "f32[3,64,128]" not in text


def test_the_prefill_program_is_a_loop_too(model):
    text = str(jax.make_jaxpr(model._prefill_fn)(
        model.params, jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    assert text.count("scan[") == 2
    # the layer's products are traced once, not once a pass
    assert text.count("dot_general") < 20


def test_the_admission_write_is_donated_and_writes_one_slot(model):
    cache = model.make_cache(3, (128,))
    k = np.random.default_rng(0).normal(
        size=(ENTRIES, 64, HEADS, D)).astype(np.float32)
    cache._k = [jax.device_put(np.full((ENTRIES, 3, C, 128), 7, np.float32),
                               cache.device)]
    old_k, old_v = cache._k[0], cache._v[0]
    cache.write_prompt(1, [k], [2 * k], 40, start=16)
    # the buffers went into the program and came back written in place
    assert old_k.is_deleted() and old_v.is_deleted()
    got = np.asarray(cache._k[0])
    np.testing.assert_array_equal(
        got[:, 1, :, 16:80], k.reshape(ENTRIES, 64, C).swapaxes(1, 2))
    keep = np.ones(got.shape, bool)
    keep[:, 1, :, 16:80] = False
    assert (got[keep] == 7).all() and cache.positions[1] == 40
    lowered = kv_cache._make_write_rows().lower(
        [jax.ShapeDtypeStruct((ENTRIES, 3, C, 128), jnp.float32)] * 2,
        [jax.ShapeDtypeStruct((ENTRIES, 64, HEADS, D), jnp.float32)] * 2,
        np.int32(0), np.int32(0))
    assert lowered.as_text().count("tf.aliasing_output") == 2


def test_the_write_is_donated_for_the_unstacked_families_too():
    cache = kv_cache.PagedKVCache(2, 2, 8, 2, buckets=(32,), prefix_slots=0)
    old = list(cache._k + cache._v)
    rows = [np.ones((8, 2, 8), np.float32)] * 2
    cache.write_prompt(1, rows, rows, 5)
    assert all(b.is_deleted() for b in old)
    assert [b.shape for b in cache._k] == [(2, 16, 32)] * 2
    assert float(np.asarray(cache.k(1))[1, :, :8].min()) == 1.0
    assert float(np.abs(np.asarray(cache.k(1))[0]).max()) == 0.0


# ---------------------------------------------------------------------------
# planted faults: each moves the system away from the reference
# ---------------------------------------------------------------------------

def _fault_three_loop_steps_for_four(monkeypatch, cfg):
    return dict(cfg, loop_steps=cfg["loop_steps"] - 1)


def _fault_branch_norm_left_out(monkeypatch, cfg):
    monkeypatch.setattr(ouro, "residual",
                        lambda x, branch, g, eps: x + branch.astype(x.dtype))
    return cfg


def _fault_interleaved_rope(monkeypatch, cfg):
    monkeypatch.setattr(ouro, "rope", c2.rope)
    return cfg


def _fault_final_norm_once_at_the_end(monkeypatch, cfg):
    real, last = ouro.loop_output, cfg["loop_steps"] - 1
    monkeypatch.setattr(
        ouro, "loop_output", lambda params, x, t, cfg: jnp.where(
            t == last, real(params, x, t, cfg), x))
    return cfg


@pytest.mark.parametrize("plant", [
    _fault_three_loop_steps_for_four, _fault_branch_norm_left_out,
    _fault_interleaved_rope, _fault_final_norm_once_at_the_end],
    ids=lambda f: f.__name__[7:])
def test_a_planted_fault_is_refused(monkeypatch, params, net, plant):
    toks = prompt(17, 60)
    want, _ = ref.forward(params, toks, net.config)
    sound, _ = ouro.forward_logits(params, jnp.asarray(toks), net.config)
    assert rel(sound, want) < TOL
    faulty = plant(monkeypatch, dict(net.config))
    got, _ = ouro.forward_logits(params, jnp.asarray(toks), faulty)
    assert rel(got, want) > 50 * TOL


def test_an_entry_index_that_forgets_the_step_is_refused(monkeypatch, net,
                                                         params):
    """Pass (t, l) writing and reading entry (t - 1, l): what it attends
    at the earlier positions is another loop step's K and V."""
    monkeypatch.setattr(
        loop, "_entry", lambda t, l, n: jnp.maximum(t - 1, 0) * n + l)
    faulty = serving.DecodeModel.from_block(net)   # traces its own step
    prompts = [prompt(13, 20)]
    seqs, answers, cache = decode_through_the_cache(faulty, prompts, 8)
    worst, _ = worst_against_the_reference(
        faulty, params, seqs, prompts, answers, cache)
    assert worst > 50 * TOL


# ---------------------------------------------------------------------------
# spans and the counter
# ---------------------------------------------------------------------------

def test_spans_and_the_counter_equal_a_hand_count(model):
    engine = new_engine(model, max_slots=2)
    before = metrics.value("mxnet_gen_loop_steps_total")
    t0 = time.perf_counter()
    out = run_all(engine, engine.submit(prompt(9, 70), max_new_tokens=6),
                  engine.submit(prompt(4, 71), max_new_tokens=3))
    assert [len(o) for o in out] == [6, 3]
    said = [s for s in tracing.spans() if s["t_begin"] >= t0]
    launches = [s["attrs"] for s in said
                if s["name"] == "model.step.dispatch"]
    prefills = [s["attrs"] for s in said if s["name"] == "model.prefill"]
    # the first token of each request is prefill's: 5 decode steps serve
    # both requests' remaining 5 + 2 tokens
    assert len(launches) == 5 and len(prefills) == 2
    for attrs in launches + prefills:
        assert (attrs["family"], attrs["loop_steps"],
                attrs["layer_passes"]) == ("loop", STEPS, ENTRIES)
    assert metrics.value("mxnet_gen_loop_steps_total") - before \
        == sum(a["loop_steps"] for a in launches) == 5 * STEPS
    # every entry is read by extent: one block of the 64-row bucket a
    # slot and an entry
    assert all(a["row_blocks"] == a["row_blocks_all"] == 2 * ENTRIES
               for a in launches)


def test_a_family_without_a_loop_says_none_of_it():
    plain = serving.DecodeModel.from_block(tiny_gpt(VOCAB))
    assert type(plain) is serving.gpt.GPTDecodeModel \
        and plain.span_attrs == {}
    before = metrics.value("mxnet_gen_loop_steps_total")
    engine = serving.GenerationEngine(plain, max_slots=2, kv_buckets=(64,),
                                      prefix_slots=0, max_tokens=8)
    t0 = time.perf_counter()
    run_all(engine, engine.submit(prompt(7), max_new_tokens=4))
    assert metrics.value("mxnet_gen_loop_steps_total") == before
    said = [s for s in tracing.spans() if s["t_begin"] >= t0
            and s["name"] in ("model.step.dispatch", "model.prefill")]
    assert said and not any("loop_steps" in s.get("attrs", {})
                            for s in said)


# ---------------------------------------------------------------------------
# the slot manager
# ---------------------------------------------------------------------------

def test_from_block_picks_the_family(model):
    assert type(model) is loop.LoopDecodeModel and model.family == "loop"
    d = model.describe()
    assert (d["loop_steps"], d["layer_passes"], d["exit_threshold"],
            d["max_prompt"]) == (STEPS, ENTRIES, 1.0, 1024)


def test_alone_equals_in_a_full_batch(model):
    p = prompt(11, 40)
    alone, = run_all(*(lambda e: (e, e.submit(p, max_new_tokens=20)))(
        new_engine(model)))
    engine = new_engine(model)
    streams = [engine.submit(q, max_new_tokens=20)
               for q in [p] + [prompt(n, n) for n in (4, 19, 37)]]
    assert engine.max_slots == len(streams)
    assert run_all(engine, *streams)[0] == alone


def test_a_freed_slot_leaks_nothing_into_the_next_request(model):
    p = prompt(6, 41)
    alone, = run_all(*(lambda e: (e, e.submit(p, max_new_tokens=12)))(
        new_engine(model, max_slots=1)))
    engine = new_engine(model, max_slots=1)
    first = engine.submit(prompt(50, 42), max_new_tokens=40)
    second = engine.submit(p, max_new_tokens=12)
    out = run_all(engine, first, second)
    assert len(out[0]) == 40 and out[1] == alone


LOOP_MIX = [
    dict(prompt=prompt(11, 50), max_new_tokens=30, at=0),
    dict(prompt=prompt(3, 51), max_new_tokens=6, at=0),
    dict(prompt=prompt(20, 52), max_new_tokens=12, at=2),
    dict(prompt=prompt(5, 53), max_new_tokens=4, at=5),
    dict(prompt=prompt(40, 54), max_new_tokens=30, at=26),
]
LANES = [dict(method="top_k", top_k=7, temperature=0.8, seed=11),
         dict(method="greedy"),
         dict(method="sample", temperature=1.3, seed=2 ** 31 - 5),
         dict(method="top_p", top_p=0.85, seed=3),
         dict(method="top_k", top_k=3, seed=4)]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_one_step_in_flight_changes_no_transcript(model, sampled):
    mix = [dict(r, **(lane if sampled else {}))
           for r, lane in zip(LOOP_MIX, LANES)]
    engine = new_engine(model, max_slots=2)
    counted = StepCounters()
    streams = run_staggered(engine, mix)
    moved = counted.moved()
    for s, r in zip(streams, mix):
        kw = {k: v for k, v in r.items() if k not in ("at", "prompt")}
        assert (s.result(), s.finish_reason) == serial_transcript(
            model, engine, r["prompt"], **kw)
    assert moved["ahead"] >= 25 and moved["discarded"] == 0
    assert moved["admissions_ahead"] >= 1
    assert moved["admit"] == moved["finish"] == 0
    assert moved["ahead"] + counted.fallbacks() == moved["iterations"]
    check_log(engine, streams)


def test_the_cache_is_one_stacked_buffer_a_side(model):
    engine = new_engine(model)
    cache = engine.cache
    d = cache.describe()
    assert d["kinds"] == {"rows": ENTRIES} and d["layers"] == ENTRIES
    assert d["layout"] == "(layers, max_slots, heads*head_dim, bucket)"
    assert d["bytes"] == {"rows": 2 * ENTRIES * 4 * C * 64 * 4,
                          "window": 0, "state": 0}
    assert metrics.value("mxnet_gen_cache_bytes", kind="rows") \
        == d["bytes"]["rows"]
    assert [b.shape for b in cache._k + cache._v] == [(ENTRIES, 4, C, 64)] * 2
    assert cache.k(4).shape == (4, C, 64)
    # every entry is read by each slot's extent, in the appended
    # walk's blocks: an eighth of the bucket, 256 of 2048
    pos = np.array([0, 600, 1000, 2000])
    assert model.row_blocks(pos, 2048) == (ENTRIES * (1 + 3 + 4 + 8),
                                           ENTRIES * 4 * 8)
    assert model.row_blocks(pos[:3], 1024) == (ENTRIES * (1 + 5 + 8),
                                               ENTRIES * 3 * 8)


def test_the_step_bytes_by_hand():
    """``chipbench/harness/loop_bytes.py`` on the published shapes, by
    ISSUE 35's arithmetic."""
    from chipbench.harness import loop_bytes
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro_2_6b.json")) as f:
        arch = json.load(f)["arch"]
    assert loop_bytes.entries(arch) == 192
    assert loop_bytes.layer_bytes(arch, 2) == 2 * 51_388_416
    assert loop_bytes.row_bytes(arch, 2) * 192 == 1_572_864
    # the layers four times, the final norm with them, the head once
    assert loop_bytes.step_weight_bytes(arch, 2) == \
        4 * (48 * 102_776_832 + 4096) + 49152 * 2048 * 2
    samples = [np.array([100, 600]), np.array([300])]
    assert loop_bytes.live_row_equivalents(samples, arch) == 500 * 192
    assert loop_bytes.attn_bytes(samples, arch, 2) == 500 * 1_572_864
    assert loop_bytes.attn_bytes([], arch, 2) is None
