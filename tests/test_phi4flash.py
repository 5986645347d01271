"""The Phi-4-mini-flash (SambaY) family: the zoo model, its serving path
(``serving.hybrid`` over a ``PagedKVCache`` of four kinds) and the
benchmark's job for it, at a CPU size that keeps all five kinds of
layer (8 layers, width 64, window 8), against the plain reference in
``tests/reference_phi4flash.py``."""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics, serving, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import phi4flash as pf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import reference_phi4flash as ref                           # noqa: E402
from serial_decode import (StepCounters, check_log,         # noqa: E402
                           run_staggered, serial_transcript)

VOCAB, WINDOW = 503, 8
TOL = 2e-5      # float32 system against the float32 reference


@pytest.fixture(scope="module")
def net():
    mx.random.seed(7)
    net = pf.get_phi4flash("tiny")
    net.collect_params().setattr("grad_req", "null")
    net.initialize()
    return net


@pytest.fixture(scope="module")
def model(net):
    return serving.DecodeModel.from_block(net)


@pytest.fixture(scope="module")
def params(net):
    return pf._collect(net)


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

def test_the_tiny_size_keeps_all_five_kinds(net):
    assert net.config["kinds"] == ["mamba", "window", "mamba", "window",
                                   "mamba", "full", "gmu", "cross"]


def test_the_published_layer_pattern():
    kinds = pf.layer_kinds(32, 2)
    assert [kinds.count(k) for k in pf.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert all(kinds[i] == "window" for i in range(1, 16, 2))
    assert all(kinds[i] == "gmu" for i in range(18, 32, 2))
    assert all(kinds[i] == "cross" for i in range(19, 32, 2))


def test_parameter_count_at_the_published_sizes_is_3_85_b():
    big = pf.get_phi4flash("phi4_mini_flash", dtype="bfloat16")
    # shapes only: nothing is initialised
    assert all(p._data is None for p in big.collect_params().values())
    n = big.num_parameters()
    assert n == 3_852_562_944
    assert round(n / 1e9, 2) == 3.85
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "phi4_mini_flash.json")) as f:
        config = json.load(f)
    assert f"{n:,}" in config["parameters"]
    assert config["reduced"] == [] and config["vocab_size"] == 200064


def test_forward_matches_the_reference(net, params):
    toks = np.stack([prompt(21, 1), prompt(21, 2)])
    got = net(mx.np.array(toks)).asnumpy()
    want = np.stack([np.asarray(ref.forward(params, t, net.config))
                     for t in toks])
    assert got.dtype == np.float32 and rel(got, want) < TOL


def test_forward_is_differentiable(net):
    w = net.layers[0].A_log.data()
    w.attach_grad()
    with mx.autograd.record():
        loss = net(mx.np.array(prompt(9)[None])).sum()
    loss.backward()
    assert float(np.abs(w.grad.asnumpy()).max()) > 0
    w.attach_grad("null")


def test_the_two_copies_of_the_reference_agree(params, net):
    with open(os.path.join(ROOT, "tests", "reference_phi4flash.py")) as a, \
            open(os.path.join(ROOT, "chipbench", "harness",
                              "reference_phi4flash.py")) as b:
        assert a.read() == b.read()
    from chipbench.harness import reference_phi4flash as copy
    ids = prompt(19, 3)
    np.testing.assert_array_equal(
        np.asarray(ref.forward(params, ids, net.config)),
        np.asarray(copy.forward(params, ids, net.config)))


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
    """Prefill, then decoding across the window's edge and (30 + 40)
    across a growth of the full layer's rows."""
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


@pytest.mark.parametrize("n", [5, 13, 64])
def test_padded_prefill_equals_unpadded_prefill(model, n):
    """Logits, recurrence state, conv tail, window rings and the full
    layer's rows AT THE PROMPT'S REAL LENGTH, whatever the padding."""
    import jax.numpy as jnp
    p = prompt(n, 20 + n)
    padded = model.prefill(p, 128)
    # the same program at the prompt's own length: no padding at all
    exact = model._prefill_fn(model.params, jnp.asarray(p),
                              np.int32(n))
    assert rel(padded[0], exact[0]) < TOL
    for name in ("ssm", "conv"):
        for a, b in zip(padded[3][name], exact[3][name]):
            assert rel(a, b) < TOL, name
    live = np.arange(min(n, WINDOW))        # columns that hold a position
    cols = (np.arange(max(0, n - WINDOW), n) % WINDOW)
    assert sorted(cols) == sorted(live)
    for name in ("wk", "wv"):
        for a, b in zip(padded[3][name], exact[3][name]):
            assert rel(np.asarray(a)[:, cols], np.asarray(b)[:, cols]) < TOL
    for a, b in zip(padded[1] + padded[2], exact[1] + exact[2]):
        assert rel(np.asarray(a)[:n], np.asarray(b)[:n]) < TOL


def test_prefill_state_is_the_reference_state_at_the_real_length(model,
                                                                 params):
    n = 13
    p = prompt(n, 33)
    state = model.prefill(p, 64)[3]
    _, held = ref.hidden_states(params, p, model.cfg)
    mamba = [h for h, k in zip(held, model.kinds) if k == "mamba"]
    for got, want in zip(state["ssm"], mamba):
        assert rel(got, want) < TOL
    windows = [h for h, k in zip(held, model.kinds) if k == "window"]
    rows = np.arange(n - WINDOW, n)
    for got_k, got_v, (k, v) in zip(state["wk"], state["wv"], windows):
        assert rel(np.asarray(got_k).T[rows % WINDOW],
                   np.asarray(k)[rows]) < TOL
        assert rel(np.asarray(got_v).T[rows % WINDOW],
                   np.asarray(v)[rows]) < TOL


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
    # a longer request first: it fills the window rings, moves the
    # state and grows the rows; then the same slot serves ``p``
    first = engine.submit(prompt(50, 42), max_new_tokens=40)
    second = engine.submit(p, max_new_tokens=12)
    out = run_all(engine, first, second)
    assert len(out[0]) == 40 and out[1] == alone
    # the long one grew the rows
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 1


# two slots; prompts on both sides of the window of 8, answers that
# cross it; the last arrives into a slot that has stood free and grows
# the full layer's rows with a step in flight
HYBRID_MIX = [
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
    """The engine launches step N+1 from step N's token array before it
    reads N back; every stream still equals the request decoded alone
    by ``model.step``, launch-wait-read: state, rings and rows, greedy
    and with sampling lanes in the same batch."""
    mix = [dict(r, **(lane if sampled else {}))
           for r, lane in zip(HYBRID_MIX, LANES)]
    engine = new_engine(model, max_slots=2)
    counted = StepCounters()
    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    streams = run_staggered(engine, mix)
    moved = counted.moved()
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 1
    for s, r in zip(streams, mix):
        kw = {k: v for k, v in r.items() if k not in ("at", "prompt")}
        assert (s.result(), s.finish_reason) == serial_transcript(
            model, engine, r["prompt"], **kw)
    assert moved["ahead"] >= 25 and moved["discarded"] == 0
    assert moved["finish"] >= 2 and moved["admit"] >= 1
    assert moved["ahead"] + counted.fallbacks() == moved["iterations"]
    check_log(engine, streams)


def test_a_late_eos_leaks_nothing_and_the_slot_is_installed_anew(model):
    """EOS at step N is read after N+1 was launched: N+1's token is
    discarded, and its column, state update and ring write land in a
    slot whose next owner has state, rings and rows installed whole."""
    engine = new_engine(model, max_slots=1)
    long_p, short_p = prompt(50, 42), prompt(6, 41)
    base, _ = serial_transcript(model, engine, long_p, 40)
    at = next(i for i in range(3, 30) if base[i] not in base[:i])
    want_second = serial_transcript(model, engine, short_p, 12)
    counted = StepCounters()
    n0 = metrics.value("mxnet_gen_state_installs_total")
    first = engine.submit(long_p, max_new_tokens=40, eos_token=base[at])
    second = engine.submit(short_p, max_new_tokens=12)
    out = run_all(engine, first, second)
    assert (out[0], first.finish_reason) == (base[:at + 1], "eos")
    assert (out[1], second.finish_reason) == want_second
    moved = counted.moved()
    assert moved["discarded"] == 1
    delivered = at + 1 + 12
    assert moved["decode_tokens"] + moved["prefill_tokens"] == delivered
    assert moved["sampled"] == delivered
    assert metrics.value("mxnet_gen_state_installs_total") == n0 + 2
    check_log(engine, [first, second])


def test_cache_accounting_by_kind(model):
    engine = new_engine(model)
    cache = engine.cache
    d = cache.describe()
    assert d["kinds"] == {"rows": 1, "window": 2, "state": 3, "none": 2}
    S, C = 4, model.cfg["num_kv_heads"] * model.cfg["head_dim"]
    di, n, k = (model.cfg[x] for x in ("d_inner", "d_state", "d_conv"))
    assert d["bytes"] == {
        "rows": 2 * 1 * S * C * 64 * 4,
        "window": 2 * 2 * S * C * WINDOW * 4,
        "state": 3 * S * di * (n + k - 1) * 4}
    assert [b.shape for b in cache.state["wk"]] == [(S, C, WINDOW)] * 2
    assert [b.shape for b in cache.state["ssm"]] == [(S, di, n)] * 3
    assert [b.shape for b in cache.state["conv"]] == [(S, di, k - 1)] * 3
    assert len(cache._k) == len(cache._v) == 1      # the full layer only
    for kind in ("rows", "window", "state"):
        assert metrics.value("mxnet_gen_cache_bytes", kind=kind) == \
            d["bytes"][kind]

    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    fixed = {name: [b.shape for b in bufs]
             for name, bufs in cache.state.items()}
    engine.submit(prompt(40, 43), max_new_tokens=40)
    while metrics.value("mxnet_gen_kv_migrations_total") == m0:
        assert engine.run_iteration()
    # growing moved the full layer's rows and nothing else
    assert cache._k[0].shape == (S, C, 128)
    assert {name: [b.shape for b in bufs]
            for name, bufs in cache.state.items()} == fixed
    after = cache.bytes_by_kind()
    assert after["rows"] == 2 * d["bytes"]["rows"]
    assert (after["window"], after["state"]) == (d["bytes"]["window"],
                                                 d["bytes"]["state"])
    assert metrics.value("mxnet_gen_cache_bytes", kind="rows") == \
        after["rows"]
    run_all(engine)
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 1


def test_live_bytes_cap_the_window_rows(model):
    engine = new_engine(model)
    engine.submit(prompt(30, 44), max_new_tokens=3)
    engine.run_iteration()
    cache = engine.cache
    pos = int(cache.positions[0])
    assert pos > WINDOW
    row = 2 * model.cfg["num_kv_heads"] * model.cfg["head_dim"] * 4
    live = cache.live_bytes_by_kind()
    assert live["rows"] == pos * row
    assert live["window"] == WINDOW * 2 * row       # never past the cap
    assert live["state"] == cache.bytes_by_kind()["state"] // 4
    for kind, n in live.items():
        assert metrics.value("mxnet_gen_cache_live_bytes", kind=kind) == n
    run_all(engine)


@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_mode="self"), "spec_mode"),
    (dict(prefix_slots=2), "prefix_slots"),
    (dict(prefix_cache=serving.PrefixCache(2)), "prefix_slots"),
])
def test_speculation_and_prefix_cache_are_refused_by_name(model, kwargs,
                                                          match):
    with pytest.raises(MXNetError, match=match) as e:
        new_engine(model, **dict(dict(prefix_slots=None), **kwargs))
    assert "phi4flash" in str(e.value)


def test_rollback_entry_points_raise(model):
    for call in (model.verify, model.prefill_suffix):
        with pytest.raises(MXNetError, match="phi4flash"):
            call()


def test_a_prompt_past_the_prefill_limit_is_refused_at_submit(model):
    engine = new_engine(model, kv_buckets=(64, 128, 2048))
    assert engine.prompt_buckets == (64, 128, 256, 512, 1024)
    with pytest.raises(MXNetError, match="prefills in one program"):
        engine.submit(prompt(1025), max_new_tokens=4)


def test_an_admission_must_install_every_kind(model):
    engine = new_engine(model)
    logits, ks, vs, state = model.prefill(prompt(5), 64)
    with pytest.raises(MXNetError, match="install exactly that"):
        engine.cache.write_prompt(0, ks, vs, 5)


def test_the_gpt_family_is_untouched():
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    gpt = GPTModel(vocab_size=VOCAB, num_layers=2, units=64,
                   hidden_size=128, num_heads=4, max_length=128,
                   dropout=0.0)
    gpt.initialize()
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    model = serving.DecodeModel.from_block(gpt)
    assert type(model) is serving.DecodeModel and model.family == "gpt"
    engine = serving.GenerationEngine(model, max_slots=2,
                                      kv_buckets=(64, 128), prefix_slots=2)
    assert engine.cache.describe()["kinds"] == {"rows": 2}
    assert engine.cache.state == {} and engine.prompt_buckets[0] == 8
    out, = run_all(engine, engine.submit(prompt(9), max_new_tokens=5))
    assert len(out) == 5
    assert metrics.value("mxnet_gen_cache_bytes", kind="rows") == \
        2 * 2 * 2 * 64 * 64 * 4


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_spans_and_counters_of_an_admission(model):
    engine = new_engine(model)
    n0 = metrics.value("mxnet_gen_state_installs_total")
    t0 = time.perf_counter()
    run_all(engine, engine.submit(prompt(12, 50), max_new_tokens=3))
    spans = [s for s in tracing.spans() if s["t_begin"] >= t0]
    by_name = {s["name"]: s for s in spans}
    assert metrics.value("mxnet_gen_state_installs_total") == n0 + 1
    install = by_name["cache.install_state"]
    assert install["attrs"]["rows"] == WINDOW
    assert install["attrs"]["slot"] == 0
    assert by_name["model.prefill"]["attrs"]["family"] == "phi4flash"
    assert by_name["model.step.dispatch"]["attrs"]["family"] == "phi4flash"
    # the install sits beside the row write, inside the admission
    admission = by_name["engine.prefill"]
    assert admission["t_begin"] <= by_name["kv.write_prompt"]["t_begin"] \
        <= install["t_begin"] <= install["t_end"] <= admission["t_end"]
