"""The Command A+ (``cohere2_moe``) family: the zoo model, the dropless
expert layer (``parallel.moe``), its serving path (``serving.moe`` over
a ``PagedKVCache`` of window rings and rows) at a CPU size that keeps
both kinds of layer and a share of the experts (4 layers, width 64,
window 8, experts 4..8 of 16 held), against the plain reference in
``tests/reference_cohere2moe.py``."""
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
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import cohere2moe as c2
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import reference_cohere2moe as ref                           # noqa: E402
from decode_families import tiny_cohere2moe, tiny_gpt        # noqa: E402
from serial_decode import (StepCounters, check_log,          # noqa: E402
                           run_staggered, serial_transcript)

VOCAB, WINDOW, HELD, LAYERS, TOP_K = 512, 8, 4, 4, 4
TOL = 2e-5      # float32 system against the float32 reference


@pytest.fixture(scope="module")
def net():
    return tiny_cohere2moe()


@pytest.fixture(scope="module")
def model(net):
    return serving.DecodeModel.from_block(net)


@pytest.fixture(scope="module")
def params(net):
    return c2._collect(net)


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


def held_choices(scores, cfg):
    """By hand from the reference's router scores (T, experts): how many
    of each row's top-k fall on each held expert, (T, held)."""
    lo, hi = cfg["experts_held"]
    chosen = np.argsort(-np.asarray(scores), axis=-1)[:, :cfg["top_k"]]
    return (chosen[..., None] == np.arange(lo, hi)).sum(1)


# ---------------------------------------------------------------------------
# the zoo model
# ---------------------------------------------------------------------------

def test_the_tiny_size_keeps_both_kinds_and_a_share_of_the_experts(net):
    cfg = net.config
    assert cfg["kinds"] == ["window", "window", "window", "full"]
    assert cfg["experts_held"] == (4, 8) and cfg["num_experts"] == 16
    assert cfg["vocab_rows"] == VOCAB < cfg["vocab_size"]
    assert net.layers[0].router_w.shape == (16, 64)
    assert net.layers[0].expert_in.shape == (HELD, 64, 64)


def test_the_published_layer_pattern():
    kinds = c2.layer_kinds(32, 4)
    assert kinds.count("full") == 8 and kinds.count("window") == 24
    assert all(kinds[i] == "full" for i in range(3, 32, 4))


@pytest.mark.parametrize("spec,count", [
    ("command_a_plus", 218_254_938_112),
    ("command_a_plus_ep8", 4_733_292_544),
])
def test_parameter_counts_from_the_declared_shapes(spec, count):
    big = c2.get_cohere2moe(spec, dtype="bfloat16")
    # shapes only: nothing is initialised
    assert all(p._data is None for p in big.collect_params().values())
    assert big.num_parameters() == count
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "command_a_plus.json")) as f:
        config = json.load(f)
    assert f"{count:,}" in config["parameters"]


def test_a_layer_by_the_issues_arithmetic():
    cut = c2.get_cohere2moe("command_a_plus_ep8")
    layer = sum(int(np.prod(p.shape))
                for p in cut.layers[0].collect_params().values())
    outside = 4096 * 16384 + 2 * 4096 * 1024 + 16384 * 4096 \
        + 4 * 3 * 4096 * 4096 + 4096 * 128 + 4096
    assert layer == outside + 16 * 3 * 4096 * 4096
    assert round(outside / 1e6, 1) == 344.5
    assert cut.word_embed_weight.shape == (32768, 4096)


def test_bad_shares_are_refused():
    with pytest.raises(ValueError, match="experts_held"):
        c2.get_cohere2moe("tiny", experts_held=(8, 20))
    with pytest.raises(ValueError, match="unknown cohere2_moe spec"):
        c2.get_cohere2moe("nope")


def test_forward_matches_the_reference(net, params):
    toks = np.stack([prompt(21, 1), prompt(21, 2)])
    got = net(mx.np.array(toks)).asnumpy()
    want = np.stack([np.asarray(ref.forward(params, t, net.config))
                     for t in toks])
    assert got.dtype == np.float32 and got.shape == (2, 21, VOCAB)
    assert rel(got, want) < TOL


def test_forward_is_differentiable(net):
    w = net.layers[0].router_w.data()
    w.attach_grad()
    with mx.autograd.record():
        loss = net(mx.np.array(prompt(9)[None])).sum()
    loss.backward()
    assert float(np.abs(w.grad.asnumpy()).max()) > 0
    w.attach_grad("null")


def test_the_two_copies_of_the_reference_agree(params, net):
    with open(os.path.join(ROOT, "tests", "reference_cohere2moe.py")) as a, \
            open(os.path.join(ROOT, "chipbench", "harness",
                              "reference_cohere2moe.py")) as b:
        assert a.read() == b.read()
    from chipbench.harness import reference_cohere2moe as copy
    ids = prompt(19, 3)
    np.testing.assert_array_equal(
        np.asarray(ref.forward(params, ids, net.config)),
        np.asarray(copy.forward(params, ids, net.config)))


def test_rope_turns_the_window_layers_and_not_the_full_layer(params, net):
    """q and k of a window layer are the unrotated projections turned by
    the position; on the full layer they are the projections."""
    cfg = net.config
    h = jax.random.normal(jax.random.PRNGKey(0), (6, cfg["units"]))
    pos = jnp.arange(10, 16)
    p = params["layers"][0]
    qw, kw, vw = c2.qkv(p, h, pos, "window", cfg)
    qf, kf, vf = c2.qkv(p, h, pos, "full", cfg)
    np.testing.assert_array_equal(vw, vf)
    assert rel(qw, qf) > 0.1 and rel(kw, kf) > 0.1
    # a rotation: norms of every pair kept, position 0 untouched
    assert rel(jnp.linalg.norm(kw.reshape(6, 2, 8, 2), axis=-1),
               jnp.linalg.norm(kf.reshape(6, 2, 8, 2), axis=-1)) < 1e-5
    q0, _, _ = c2.qkv(p, h, jnp.zeros(6, jnp.int32), "window", cfg)
    assert rel(q0, qf) < 1e-6
    # the reference's rotation, at the reference's positions 0..5
    want = ref.rope(jnp.asarray(kf), cfg["rope_theta"])
    got = c2.rope(kf, jnp.arange(6), cfg["rope_theta"])
    assert rel(got, want) < 1e-5


# ---------------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------------

def _routing_case(T=40, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(k[0], (T, 32))
    rw = jax.random.normal(k[1], (16, 32)) * 0.3
    w_in = jax.random.normal(k[2], (HELD, 32, 48)) * 0.2
    w_out = jax.random.normal(k[3], (HELD, 24, 32)) * 0.2
    return h, rw, w_in, w_out


def test_route_is_sigmoid_top_k_normalised_over_all_the_experts():
    h, rw, _, _ = _routing_case()
    local, weights, load, scores = moe.route(h, rw, TOP_K, (4, 8))
    s = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(rw).T))
    assert rel(scores, s) < 1e-5
    chosen = np.argsort(-s, axis=-1)[:, :TOP_K]
    top = np.take_along_axis(s, chosen, axis=-1)
    assert rel(weights, top / top.sum(-1, keepdims=True)) < 1e-5
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1, rtol=1e-5)
    want_local = np.where((chosen >= 4) & (chosen < 8), chosen - 4, HELD)
    np.testing.assert_array_equal(local, want_local)
    np.testing.assert_array_equal(
        load, (want_local[..., None] == np.arange(HELD)).sum((0, 1)))
    # most choices fall on absent experts, and none is made up for
    assert 0 < int(load.sum()) < local.size // 2


def test_invalid_tokens_are_routed_nowhere():
    h, rw, _, _ = _routing_case()
    valid = jnp.arange(40) < 25
    local, _, load, _ = moe.route(h, rw, TOP_K, (4, 8), valid)
    full, _, _, _ = moe.route(h[:25], rw, TOP_K, (4, 8))
    assert (np.asarray(local)[25:] == HELD).all()
    np.testing.assert_array_equal(np.asarray(local)[:25], full)
    assert int(load.sum()) == int((np.asarray(full) < HELD).sum())


@pytest.mark.parametrize("T", [1, 7, 40])
def test_the_two_forms_of_the_product_agree_with_a_loop(T):
    h, rw, w_in, w_out = _routing_case(T, seed=T)
    local, weights, load, _ = moe.route(h, rw, TOP_K, (4, 8))
    want = np.zeros((T, 32), np.float32)
    for t in range(T):
        for j in range(TOP_K):
            e = int(local[t, j])
            if e < HELD:
                gu = np.asarray(h[t]) @ np.asarray(w_in[e])
                act = gu[:24] / (1 + np.exp(-gu[:24])) * gu[24:]
                want[t] += float(weights[t, j]) * (act @ np.asarray(w_out[e]))
    dense = moe.dense_experts(h, local, weights, w_in, w_out)
    grouped = moe.grouped_experts(h, local, weights, load, w_in, w_out)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(dense - want).max() / scale < 1e-5
    assert np.abs(grouped - want).max() / scale < 1e-5


def test_the_two_forms_have_the_same_gradients():
    """Rows past the segments are left unwritten by the kernel (NaN in
    interpret mode): none of it reaches a gradient."""
    h, rw, w_in, w_out = _routing_case(9, seed=3)

    def grads(form):
        def f(h, w_in, w_out):
            local, weights, load, _ = moe.route(h, rw, TOP_K, (4, 8))
            y = moe.dense_experts(h, local, weights, w_in, w_out) \
                if form == "dense" else moe.grouped_experts(
                    h, local, weights, load, w_in, w_out)
            return (y ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))(h, w_in, w_out)

    for a, b in zip(grads("dense"), grads("grouped")):
        assert np.isfinite(np.asarray(b)).all()
        assert np.abs(a - b).max() <= 1e-5 * max(np.abs(a).max(), 1e-6)


def test_the_sequence_takes_the_segments_and_the_step_the_batch(
        monkeypatch, params, net):
    called = []
    for name in ("dense_experts", "grouped_experts"):
        real = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _n=name, _r=real:
                            called.append(_n) or _r(*a))
    c2.forward_logits(params, jnp.asarray(prompt(9)), net.config)
    assert called == ["grouped_experts"] * LAYERS
    del called[:]
    fresh = serving.DecodeModel.from_block(net)     # traces its own step
    fresh.step(fresh.make_cache(2, (64,)), np.zeros(2, np.int32),
               np.zeros(2, np.int32))
    assert called == ["dense_experts"] * LAYERS


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each share's routed part (its range of the experts, the program's
    functions), with attention and the shared experts counted once, adds
    up to what the reference gives for the whole layer with every
    expert held."""
    mx.random.seed(11)
    whole = c2.get_cohere2moe("tiny", experts_held=(0, 16))
    whole.collect_params().setattr("grad_req", "null")
    whole.initialize()
    cfg = whole.config
    p = c2._collect(whole)["layers"][1]
    # experts loud enough that one share left out is far past TOL
    p = dict(p, expert_in=p["expert_in"] * 10, expert_out=p["expert_out"] * 10)
    x = jax.random.normal(jax.random.PRNGKey(5), (23, cfg["units"]))
    want, _, _ = ref.layer(p, x, "window", cfg)

    h = c2._ln(x, p["ln_g"], cfg["layer_norm_eps"])
    q, k, v = c2.qkv(p, h, jnp.arange(23), "window", cfg)
    once = x + c2._mm(c2.attention_seq(q, k, v, cfg, cfg["window"]),
                      p["out_w"]) + c2.shared_experts(p, h)
    parts, loads = [], []
    for lo in range(0, 16, 2):
        local, weights, load, _ = moe.route(h, p["router_w"], cfg["top_k"],
                                            (lo, lo + 2))
        parts.append(moe.grouped_experts(
            h, local, weights, load, p["expert_in"][lo:lo + 2],
            p["expert_out"][lo:lo + 2]))
        loads.append(int(load.sum()))
    assert rel(once + sum(parts), want) < TOL
    # every choice was some share's, and no share's part is the whole
    assert sum(loads) == 23 * cfg["top_k"]
    assert all(rel(once + sum(parts) - part, want) > 50 * TOL
               for part in parts)


# ---------------------------------------------------------------------------
# planted faults: each moves the system away from the reference
# ---------------------------------------------------------------------------

def _fault_unnormalised(monkeypatch, params, cfg):
    real = moe.route

    def route(*args):
        local, weights, load, scores = real(*args)
        top = jax.lax.top_k(scores, cfg["top_k"])[0]
        return local, top, load, scores
    monkeypatch.setattr(moe, "route", route)
    return params, cfg


def _fault_shared_summed(monkeypatch, params, cfg):
    real = c2.shared_experts
    monkeypatch.setattr(c2, "shared_experts",
                        lambda p, h: real(p, h) * cfg["num_shared"])
    return params, cfg


def _fault_expert_dropped(monkeypatch, params, cfg):
    layers = [dict(p, expert_out=p["expert_out"].at[2].set(0.0))
              for p in params["layers"]]
    return dict(params, layers=layers), cfg


def _fault_window_off_by_one(monkeypatch, params, cfg):
    return params, dict(cfg, window=cfg["window"] + 1)


def _fault_rope_on_the_full_layer(monkeypatch, params, cfg):
    real = c2.qkv
    monkeypatch.setattr(
        c2, "qkv", lambda p, h, pos, kind, cfg: real(p, h, pos, "window",
                                                     cfg))
    return params, cfg


def _fault_no_rope(monkeypatch, params, cfg):
    monkeypatch.setattr(c2, "rope", lambda x, pos, theta: x)
    return params, cfg


@pytest.mark.parametrize("plant", [
    _fault_unnormalised, _fault_shared_summed, _fault_expert_dropped,
    _fault_window_off_by_one, _fault_rope_on_the_full_layer,
    _fault_no_rope], ids=lambda f: f.__name__[7:])
def test_a_planted_fault_is_refused(monkeypatch, params, net, plant):
    toks = prompt(24, 5)
    want = np.asarray(ref.forward(params, toks, net.config))
    sound = c2.forward_logits(params, jnp.asarray(toks), net.config)
    assert rel(sound, want) < TOL
    faulty_params, faulty_cfg = plant(monkeypatch, params, net.config)
    got = c2.forward_logits(faulty_params, jnp.asarray(toks), faulty_cfg)
    assert rel(got, want) > 50 * TOL


# ---------------------------------------------------------------------------
# prefill and decode against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [5, WINDOW, 20, 64])
def test_prefill_logits_match_the_reference(model, params, n):
    """Inside the window, at its edge, past it, and a full bucket."""
    p = prompt(n, n)
    got = model.prefill(p, 64)[0]
    want = np.asarray(ref.forward(params, p, model.cfg))[-1]
    assert rel(got, want) < TOL


@pytest.mark.parametrize("n,new", [(3, 24), (WINDOW - 1, 6), (30, 40)])
def test_decode_through_the_cache_matches_the_reference(model, params, n,
                                                        new):
    """Prefill, then decoding across the window's edge (the ring wraps:
    keys stored rotated, read in ring order) and (30 + 40) across a
    growth of the full layer's rows."""
    engine = new_engine(model)
    p = prompt(n, 10 + n)
    got, = run_all(engine, engine.submit(p, max_new_tokens=new))
    want = np.asarray(ref.forward(
        params, np.concatenate([p, got[:-1]]), model.cfg))[n - 1:]
    top2 = np.sort(want, axis=-1)[:, -2:]
    decisive = top2[:, 1] - top2[:, 0] > 1e-4
    assert decisive.sum() >= new - 2
    assert [t for t, d in zip(got, decisive) if d] == \
        [int(t) for t, d in zip(want.argmax(-1), decisive) if d]


def test_the_step_leaves_the_references_rotated_keys_in_the_ring(model,
                                                                 params):
    """After a prefill and forced steps across the window's edge, ring
    column p % window of every window layer holds the reference's
    ROTATED key of position p, and the rows the full layer's plain
    ones."""
    n, steps = 5, 14
    p, forced = prompt(n, 60), prompt(steps, 61)
    cache = model.make_cache(2, (64,))
    _, ks, vs, state = model.prefill(p, 64)
    cache.write_prompt(1, ks, vs, n, state=state)
    cache.positions[0] = 0
    for t in forced:
        model.step(cache, np.array([0, t], np.int32),
                   np.maximum(cache.positions, 0))
        cache.positions[1] += 1
    end = n + steps
    _, held, _ = ref.hidden_states(params, np.concatenate([p, forced]),
                                   model.cfg)
    seen = np.arange(end - WINDOW, end)
    rings = [h for h, k in zip(held, model.kinds) if k == "window"]
    for i, (k, v) in enumerate(rings):
        assert rel(np.asarray(cache.state["wk"][i][1]).T[seen % WINDOW],
                   np.asarray(k).reshape(end, -1)[seen]) < TOL
        assert rel(np.asarray(cache.state["wv"][i][1]).T[seen % WINDOW],
                   np.asarray(v).reshape(end, -1)[seen]) < TOL
    k, v = held[3]
    assert rel(np.asarray(cache.k(0)[1]).T[:end],
               np.asarray(k).reshape(end, -1)) < TOL
    assert rel(np.asarray(cache.v(0)[1]).T[:end],
               np.asarray(v).reshape(end, -1)) < TOL


@pytest.mark.parametrize("n", [5, 13, 64])
def test_padded_prefill_equals_unpadded_prefill(model, n):
    """Logits, rings, rows and the load AT THE PROMPT'S REAL LENGTH,
    whatever the padding."""
    p = prompt(n, 20 + n)
    padded = model._prefill_fn(
        model.params, jnp.asarray(np.pad(p, (0, 128 - n))), np.int32(n))
    exact = model._prefill_fn(model.params, jnp.asarray(p), np.int32(n))
    assert rel(padded[0], exact[0]) < TOL
    cols = (np.arange(max(0, n - WINDOW), n) % WINDOW)
    for name in ("wk", "wv"):
        for a, b in zip(padded[3][name], exact[3][name]):
            assert rel(np.asarray(a)[:, cols], np.asarray(b)[:, cols]) < TOL
    for a, b in zip(padded[1] + padded[2], exact[1] + exact[2]):
        assert rel(np.asarray(a)[:n], np.asarray(b)[:n]) < TOL
    np.testing.assert_array_equal(padded[4], exact[4])


# ---------------------------------------------------------------------------
# the expert load: what the programs hand back, against a hand count
# ---------------------------------------------------------------------------

def test_prefill_says_the_load_of_the_real_tokens(model, params):
    n = 13
    p = prompt(n, 33)
    t0 = time.perf_counter()
    with tracing.span("test.admission"):
        model.prefill(p, 64)
    span = [s for s in tracing.spans()
            if s["name"] == "model.prefill" and s["t_begin"] >= t0][-1]
    _, _, scores = ref.hidden_states(params, p, model.cfg)
    want = np.stack([held_choices(s, model.cfg).sum(0) for s in scores])
    np.testing.assert_array_equal(model.last_prefill_load, want)
    assert span["attrs"]["expert_assignments"] == want.sum() > 0
    assert span["attrs"]["experts_hit"] == (want > 0).sum()
    assert span["attrs"]["family"] == "cohere2moe"


def test_every_admission_says_its_load_through_the_engine(model, params):
    """Admissions queued behind a step read the prefill's load back with
    their first token: every admission's ``model.prefill`` span still
    says ``expert_assignments`` and ``experts_hit``, equal to the hand
    count from the reference's router scores.  ``prefill`` called
    directly still hands back numpy logits and sets
    ``last_prefill_load`` (what the benchmark's correctness job
    reads)."""
    mix = [dict(prompt=prompt(11, 80), max_new_tokens=12, at=0),
           dict(prompt=prompt(7, 81), max_new_tokens=3, at=0),
           dict(prompt=prompt(19, 82), max_new_tokens=4, at=2),
           dict(prompt=prompt(5, 83), max_new_tokens=3, at=3)]
    engine = new_engine(model, max_slots=2)
    counted = StepCounters()
    t0 = time.perf_counter()
    run_staggered(engine, mix)
    assert counted.moved()["admissions_ahead"] >= 1
    said = sorted((s for s in tracing.spans() if s["name"] == "model.prefill"
                   and s["t_begin"] >= t0), key=lambda s: s["t_begin"])
    assert len(said) == len(mix)
    wants = []
    for s, r in zip(said, mix):      # the queue admits in arrival order
        _, _, scores = ref.hidden_states(params, r["prompt"], model.cfg)
        want = np.stack([held_choices(x, model.cfg).sum(0) for x in scores])
        assert s["attrs"]["expert_assignments"] == want.sum() > 0
        assert s["attrs"]["experts_hit"] == (want > 0).sum()
        wants.append(want)
    logits, *_ = model.prefill(mix[0]["prompt"], 64)
    assert isinstance(logits, np.ndarray) and logits.shape == (VOCAB,)
    np.testing.assert_array_equal(model.last_prefill_load, wants[0])


def test_a_steps_load_equals_a_hand_count(model, params):
    """Two slots: one live, one free (it rides along at token 0,
    position 0 and is routed like any other).  The step's load is, by
    layer and held expert, the live token's choices from the
    reference's router scores plus the free slot's."""
    cfg = model.cfg
    n = 9
    p, tok = prompt(n, 70), 123
    cache = model.make_cache(2, (64,))
    _, ks, vs, state = model.prefill(p, 64)
    cache.write_prompt(0, ks, vs, n, state=state)
    names = ("mxnet_gen_expert_assignments_total",
             "mxnet_gen_expert_offered_total",
             "mxnet_gen_experts_hit_total", "mxnet_gen_expert_slots_total")
    before = [metrics.value(name) for name in names]
    t0 = time.perf_counter()
    with tracing.span("test.iteration"):
        out = model.step(cache, np.array([tok, 0], np.int32),
                         np.array([n, 0], np.int32))
    assert out.shape == (2,) and out.dtype == np.int32
    _, _, live = ref.hidden_states(params, np.append(p, tok), cfg)
    _, _, free = ref.hidden_states(params, np.array([0]), cfg)
    load = np.stack([held_choices(a[-1:], cfg)[0]
                     + held_choices(b[-1:], cfg)[0]
                     for a, b in zip(live, free)])
    assert load.shape == (LAYERS, HELD) and load.sum() > 0
    np.testing.assert_array_equal(model.last_load, load)
    moved = [metrics.value(name) - b for name, b in zip(names, before)]
    assert moved == [load.sum(), 2 * TOP_K * LAYERS, (load > 0).sum(),
                     LAYERS * HELD]
    span = [s for s in tracing.spans()
            if s["name"] == "model.step.readback" and s["t_begin"] >= t0][-1]
    assert span["attrs"] == {
        "expert_assignments": load.sum(), "experts_hit": (load > 0).sum(),
        "expert_load_max": load.max(), "expert_slots": LAYERS * HELD}


def test_a_family_without_experts_says_none_of_it():
    from mxnet_tpu.gluon.model_zoo import phi4flash as pf
    mx.random.seed(1)
    other = pf.get_phi4flash("tiny")
    other.collect_params().setattr("grad_req", "null")
    other.initialize()
    hybrid = serving.DecodeModel.from_block(other)
    assert type(hybrid).__name__ == "HybridDecodeModel"
    before = metrics.value("mxnet_gen_expert_offered_total")
    engine = serving.GenerationEngine(hybrid, max_slots=2,
                                      kv_buckets=(64,), prefix_slots=0,
                                      max_tokens=8)
    t0 = time.perf_counter()
    run_all(engine, engine.submit(prompt(7) % 503, max_new_tokens=4))
    assert metrics.value("mxnet_gen_expert_offered_total") == before
    said = [s for s in tracing.spans() if s["t_begin"] >= t0
            and s["name"] in ("model.step.readback", "model.prefill")]
    assert said and not any(
        k.startswith("expert") for s in said for k in s.get("attrs", {}))


# ---------------------------------------------------------------------------
# the slot manager
# ---------------------------------------------------------------------------

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
    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    # a longer request first: it wraps the rings and grows the rows;
    # then the same slot serves ``p``
    first = engine.submit(prompt(50, 42), max_new_tokens=40)
    second = engine.submit(p, max_new_tokens=12)
    out = run_all(engine, first, second)
    assert len(out[0]) == 40 and out[1] == alone
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 1


MOE_MIX = [
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
    """The engine feeds step N+1 the array step N handed back (tokens
    and load in one) before it reads N; every stream still equals the
    request decoded alone by ``model.step``, launch-wait-read."""
    mix = [dict(r, **(lane if sampled else {}))
           for r, lane in zip(MOE_MIX, LANES)]
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


def test_cache_and_blocks_by_kind(model):
    engine = new_engine(model)
    cache = engine.cache
    d = cache.describe()
    assert d["kinds"] == {"rows": 1, "window": 3}
    S, C = 4, model.cfg["num_kv_heads"] * model.cfg["head_dim"]
    assert d["bytes"] == {"rows": 2 * S * C * 64 * 4,
                          "window": 2 * 3 * S * C * WINDOW * 4, "state": 0}
    assert [b.shape for b in cache.state["wk"]] == [(S, C, WINDOW)] * 3
    assert len(cache._k) == len(cache._v) == 1
    # every cache is read by extent: the rings' one block of 8 a slot,
    # the rows' one block of 64
    pos = np.array([0, 3, 20, 63])
    assert model.row_blocks(pos, 64) == (3 * 4 + 4, 3 * 4 + 4)
    from mxnet_tpu.ops.pallas import decode_attention as da
    big = type("M", (), dict(cfg=dict(window=4096), kinds=model.kinds,
                             n_layers=4))()
    pos = np.array([0, 600, 4000, 5000])
    ring = da.blocks_read(np.minimum(pos, 4095), 4096)
    rows = da.blocks_read(pos, 8192)
    assert serving.moe.MoEDecodeModel.row_blocks(big, pos, 8192) == (
        3 * ring[0] + rows[0], 3 * ring[1] + rows[1])
    assert ring[0] == 1 + 2 + 8 + 8 and rows[0] == 1 + 2 + 8 + 10


def test_the_other_families_are_untouched():
    """A GPT block is its own family with its own programs and takes
    and returns plain (S,) token vectors; a GPT block with ``MoEDense``
    inside is refused with the families that are served."""
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    plain = serving.DecodeModel.from_block(tiny_gpt(VOCAB))
    assert type(plain) is serving.gpt.GPTDecodeModel \
        and plain.family == "gpt"
    toks = np.arange(3, dtype=np.int32)
    assert plain._step_tokens(toks) is toks
    assert plain._read_step(None, toks) is toks
    assert plain._prefill_extras([1, 2]) == ([1, 2], None)
    assert plain.row_blocks(np.zeros(2, np.int32), 64) is None
    with pytest.raises(MXNetError, match="Cohere2MoEModel"):
        serving.DecodeModel.from_block(mx.gluon.nn.Dense(4))
    sparse = GPTModel(vocab_size=VOCAB, num_layers=2, units=64,
                      hidden_size=128, num_heads=4, max_length=128,
                      dropout=0.0, moe_every_n=1, moe_experts=4)
    sparse.initialize()
    sparse(mx.np.zeros((1, 4), dtype="int32"))
    with pytest.raises(MXNetError, match="cohere2moe"):
        serving.DecodeModel.from_block(sparse)


def test_describe_says_the_share(model):
    d = model.describe()
    assert d["family"] == "cohere2moe"
    assert (d["experts"], d["experts_held"], d["experts_per_token"],
            d["shared_experts"]) == (16, [4, 8], TOP_K, 2)
    assert d["layer_kinds"] == {"window": 3, "full": 1}
    assert d["vocab_size"] == VOCAB
