"""ISSUE 12: on-device sampling + shared-prefix KV cache for the
GenerationEngine.

The invariants under test:

* sampled decode (temperature / top_k / top_p under per-slot
  counter-PRNG keys) is TOKEN-IDENTICAL to the host-side oracle
  ``model_zoo.generation._select`` driven over the uncompiled full
  forward with the same ``fold_in(PRNGKey(seed), index)`` key stream;
* per-request sampling-parameter changes ride the ONE compiled decode
  step (0 XLA compiles after warmup) and the readback stays (S,) int32;
* same-seed streams are identical run-to-run AND across a seeded
  ``serving.worker`` kill (the PR-7 resurrection contract extended to
  sampling: replay the key stream from seed + emitted-token count,
  dedupe at the TokenStream index boundary);
* shared-prefix admission (copy resident rows + suffix prefill, or a
  pure copy for an identical prompt) never changes tokens — byte
  identical vs a prefix-cache-off engine — and never perturbs resident
  sequences, including across a mid-flight LRU eviction;
* the HTTP surface 400s out-of-range sampling values on both the
  stream and collect paths.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, metrics, serving
from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                               GenerationServer, PrefixCache)
from mxnet_tpu.serving.kv_cache import prefix_key

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from serial_decode import (StepCounters, run_staggered,     # noqa: E402
                           serial_transcript)

VOCAB = 97
PROMPT_A = onp.array([5, 9, 3, 17], dtype="int32")
PROMPT_B = onp.array([1, 2], dtype="int32")


@pytest.fixture(scope="module")
def gpt():
    """Tiny decoder LM, strong init (same rationale as
    tests/test_generation.py: varied, deterministic output so
    positional/sampling bugs cannot hide behind a constant stream)."""
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    mx.random.seed(0)
    net = GPTModel(vocab_size=VOCAB, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    net.initialize(mx.init.Normal(1.0))
    net(mx.np.zeros((1, 4), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def decode_model(gpt):
    return DecodeModel.from_block(gpt)


def _engine(decode_model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("kv_buckets", (16, 32, 64))
    kw.setdefault("max_tokens", 48)
    eng = GenerationEngine(decode_model, **kw)
    eng.warmup()
    return eng


def _drain(eng, *streams, max_iters=300):
    it = 0
    while not all(s.finished for s in streams) and it < max_iters:
        eng.run_iteration()
        it += 1
    assert it < max_iters, "engine did not finish the sequences"


def _reference_sampled(gpt, prompt, n, method, temperature=1.0,
                       top_k=40, top_p=0.9, seed=0, offset=0):
    """The host-side oracle: full uncompiled forward per token +
    the zoo's ``_select`` under the request's counter-key stream
    (token i draws under fold_in(PRNGKey(seed), offset + i))."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo.generation import _select

    PAD = 64
    toks = [int(t) for t in prompt]
    out = []
    for i in range(n):
        padded = toks + [0] * (PAD - len(toks))
        logits = gpt(mx.np.array(
            onp.asarray([padded], "int32"))).asnumpy()
        row = jnp.asarray(logits[0, len(toks) - 1])[None]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), offset + i)
        nxt = int(_select(row, method, temperature,
                          min(top_k, VOCAB), top_p, key)[0])
        out.append(nxt)
        toks.append(nxt)
    return out


# ---------------------------------------------------------------------------
# sampled-decode parity vs the zoo oracle, per method
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,kw", [
    ("sample", dict(temperature=1.2)),
    ("top_k", dict(temperature=0.8, top_k=5)),
    ("top_p", dict(temperature=1.1, top_p=0.7)),
])
def test_sampled_parity_vs_zoo_select(gpt, decode_model, method, kw):
    want = _reference_sampled(gpt, PROMPT_A, 8, method, seed=11, **kw)
    eng = _engine(decode_model)
    s = eng.submit(PROMPT_A, max_new_tokens=8, method=method, seed=11,
                   **kw)
    _drain(eng, s)
    assert s.result(timeout=10) == want, \
        f"{method} decode diverged from the zoo _select oracle"


@pytest.mark.parametrize("lanes", [
    [dict(method="sample", temperature=0.9, seed=5),
     dict(method="top_k", top_k=5, seed=6),
     dict(method="top_p", top_p=0.8, temperature=1.2, seed=2 ** 31 - 9),
     dict(method="top_k", top_k=2, temperature=0.7, seed=8)],
    [dict(method="greedy"), dict(method="top_p", top_p=0.6, seed=21),
     dict(method="greedy"), dict(method="sample", seed=22)],
], ids=["all_sampled", "mixed_with_greedy"])
def test_sampled_transcripts_equal_the_serial_step_loop(decode_model, lanes):
    """The in-program sampler's counter comes from the position
    operand, which the engine sends the same whether a step is launched
    from the host's tokens or from the last step's array on the device:
    sampled streams equal the request decoded alone by
    ``DecodeModel.step``, launch-wait-read, token for token."""
    mix = [dict(prompt=p, max_new_tokens=n, at=at, **lane)
           for (p, n, at), lane in zip(
               [(PROMPT_A, 26, 0), (PROMPT_B, 6, 0),
                (onp.arange(3, 12, dtype="int32"), 10, 2),
                (onp.array([9, 9, 4], "int32"), 8, 20)], lanes)]
    eng = _engine(decode_model)
    counted = StepCounters()
    streams = run_staggered(eng, mix)
    moved = counted.moved()
    for s, r in zip(streams, mix):
        kw = {k: v for k, v in r.items() if k not in ("at", "prompt")}
        assert (s.result(timeout=5), s.finish_reason) == serial_transcript(
            decode_model, eng, r["prompt"], **kw)
    # the admissions that found a step in flight were queued behind it
    assert moved["ahead"] >= 20 and moved["admissions_ahead"] >= 1
    assert moved["admit"] == moved["finish"] == 0
    assert moved["ahead"] + counted.fallbacks() == moved["iterations"]
    assert moved["sampled"] == sum(len(s.tokens) for s in streams)


def test_sampling_defaults_and_validation(decode_model):
    eng = _engine(decode_model, default_method="top_k",
                  default_top_k=500)   # clamps to vocab at submit
    s = eng.submit(PROMPT_A, max_new_tokens=4, seed=3)
    _drain(eng, s)
    assert len(s.result(timeout=10)) == 4
    assert metrics.value("mxnet_gen_sampled_tokens_total",
                         method="top_k") >= 4
    for bad in (dict(method="beam"), dict(temperature=0.0),
                dict(temperature=-1.0), dict(top_k=0),
                dict(top_p=0.0), dict(top_p=1.5),
                dict(seed=2**31), dict(seed=-2**31 - 1)):
        with pytest.raises(mx.MXNetError):
            eng.submit(PROMPT_A, max_new_tokens=4, **bad)


def test_sampling_param_changes_zero_compiles(decode_model):
    eng = _engine(decode_model)
    _drain(eng, eng.submit(PROMPT_A, max_new_tokens=4))  # settle
    c0 = metrics.value("mxnet_compile_misses_total")
    streams = [
        eng.submit(PROMPT_A, max_new_tokens=5, method=m, seed=i, **kw)
        for i, (m, kw) in enumerate([
            ("greedy", {}),
            ("sample", dict(temperature=0.6)),
            ("top_k", dict(top_k=3)),
            ("top_p", dict(top_p=0.5, temperature=1.4)),
            ("top_k", dict(top_k=20, temperature=0.9)),
        ])]
    _drain(eng, *streams)
    assert all(len(s.result(timeout=10)) == 5 for s in streams)
    assert metrics.value("mxnet_compile_misses_total") == c0, \
        "changing sampling method/params recompiled the decode step"


def test_same_seed_identical_different_seed_differs(decode_model):
    eng = _engine(decode_model)
    runs = []
    for seed in (7, 7, 8):
        s = eng.submit(PROMPT_A, max_new_tokens=12, method="sample",
                       temperature=1.3, seed=seed)
        _drain(eng, s)
        runs.append(s.result(timeout=10))
    assert runs[0] == runs[1], "same seed must reproduce the stream"
    assert runs[0] != runs[2], \
        "different seeds produced identical 12-token streams (PRNG " \
        "keys not riding the seed?)"


# ---------------------------------------------------------------------------
# same-seed streams across a seeded worker kill (resurrection + sampling)
# ---------------------------------------------------------------------------

def test_sampled_streams_identical_across_worker_death(decode_model):
    prompts = [PROMPT_A, PROMPT_B]
    kws = [dict(method="sample", temperature=1.2, seed=21),
           dict(method="top_k", top_k=7, temperature=0.9, seed=22)]
    budgets = [10, 8]

    def collect(with_kill):
        factory = lambda: _engine(decode_model)          # noqa: E731
        gs = GenerationServer(engine_factory=factory, replicas=2,
                              restart_backoff_ms=10)
        gs.start()
        try:
            if with_kill:
                # the third busy worker pass dies with sequences
                # resident — they must resurrect from their stream
                # transcripts, replaying the counter-key stream
                with faults.fault_plan("serving.worker:after=2:times=1"):
                    streams = [gs.generate(p, max_new_tokens=n, **kw)
                               for p, n, kw in zip(prompts, budgets,
                                                   kws)]
                    return [s.result(timeout=60) for s in streams]
            streams = [gs.generate(p, max_new_tokens=n, **kw)
                       for p, n, kw in zip(prompts, budgets, kws)]
            return [s.result(timeout=60) for s in streams]
        finally:
            gs.stop()

    clean = collect(with_kill=False)
    rec0 = (metrics.value("mxnet_serving_recoveries_total",
                          site="worker")
            + metrics.value("mxnet_serving_recoveries_total",
                            site="queue"))
    killed = collect(with_kill=True)
    recs = (metrics.value("mxnet_serving_recoveries_total",
                          site="worker")
            + metrics.value("mxnet_serving_recoveries_total",
                            site="queue"))
    assert faults.injected_count("serving.worker") == 0  # left scope
    assert recs > rec0, "the kill recovered nothing (did it fire?)"
    assert killed == clean, \
        "same-seed sampled streams diverged across worker death"


# ---------------------------------------------------------------------------
# shared-prefix KV cache
# ---------------------------------------------------------------------------

def _shared_prompts():
    rng = onp.random.RandomState(3)
    system = rng.randint(1, 90, (16,)).astype("int32")  # bucket-aligned
    return system, [
        onp.concatenate([system,
                         rng.randint(1, 90, (2 + i,)).astype("int32")])
        for i in range(3)]


def test_prefix_hit_skips_prefill_and_matches_cache_off(gpt,
                                                        decode_model):
    system, prompts = _shared_prompts()
    off = _engine(decode_model, prefix_slots=0)
    want = []
    for p in prompts + [system, system]:
        s = off.submit(p, max_new_tokens=6)
        _drain(off, s)
        want.append(s.result(timeout=10))

    eng = _engine(decode_model, prefix_slots=4)
    h0 = metrics.value("mxnet_gen_prefix_cache_hits_total")
    calls = {"prefill": 0}
    real_prefill = eng.model.prefill

    def counting_prefill(*a, **kw):
        calls["prefill"] += 1
        return real_prefill(*a, **kw)

    eng.model.prefill = counting_prefill
    try:
        got = []
        for p in prompts + [system, system]:
            s = eng.submit(p, max_new_tokens=6)
            _drain(eng, s)
            got.append(s.result(timeout=10))
    finally:
        eng.model.prefill = real_prefill
    assert got == want, "prefix-cache streams diverged from cache-off"
    # prompt 1 is the only cold full prefill; 2-3 ride the suffix
    # path, and the 16-token system prompt itself: the first run
    # attaches whole-prompt logits (cold), the second is a pure copy
    assert calls["prefill"] == 2, \
        f"expected 2 cold prefills, saw {calls['prefill']}"
    assert metrics.value("mxnet_gen_prefix_cache_hits_total") \
        - h0 == 3


def test_full_prompt_hit_needs_no_model_call(decode_model):
    system, _ = _shared_prompts()
    eng = _engine(decode_model, prefix_slots=4)
    s = eng.submit(system, max_new_tokens=4)     # cold: inserts+logits
    _drain(eng, s)
    first = s.result(timeout=10)
    calls = {"n": 0}
    real_prefill = eng.model.prefill
    real_suffix = eng.model.prefill_suffix

    def boom(*a, **kw):
        calls["n"] += 1
        raise AssertionError("model invoked on a full-prompt hit")

    eng.model.prefill = boom
    eng.model.prefill_suffix = boom
    try:
        s2 = eng.submit(system, max_new_tokens=4)
        _drain(eng, s2)
        assert s2.result(timeout=10) == first
    finally:
        eng.model.prefill = real_prefill
        eng.model.prefill_suffix = real_suffix
    assert calls["n"] == 0


def test_prefix_admission_and_eviction_change_no_resident_tokens(
        gpt, decode_model):
    """The PR-6 invariant re-asserted under prefix-copy admission and
    a mid-flight LRU eviction: a resident sequence's tokens never
    move because of either."""
    from tests.test_generation import _reference_greedy
    want_a = _reference_greedy(gpt, PROMPT_A, 20)
    system, prompts = _shared_prompts()
    eng = _engine(decode_model, max_slots=3, prefix_slots=1)
    sa = eng.submit(PROMPT_A, max_new_tokens=20)
    for _ in range(4):
        eng.run_iteration()                  # A is mid-decode...
    sb = eng.submit(prompts[0], max_new_tokens=4)   # cold insert
    sc = eng.submit(prompts[1], max_new_tokens=4)   # prefix-copy hit
    _drain(eng, sb, sc)
    # ...and a distinct prefix evicts the (slots=1) resident entry
    # while A still decodes
    rng = onp.random.RandomState(9)
    ev0 = metrics.value("mxnet_gen_prefix_cache_evictions_total")
    sd = eng.submit(rng.randint(1, 90, (18,)).astype("int32"),
                    max_new_tokens=4)
    _drain(eng, sa, sd)
    assert metrics.value("mxnet_gen_prefix_cache_evictions_total") \
        > ev0, "the eviction under test never happened"
    assert sa.result(timeout=10) == want_a, \
        "prefix admission/eviction perturbed a resident sequence"
    log = list(eng.iteration_log)
    admit_iters = [l["iter"] for l in log if l["admitted"]]
    assert len(admit_iters) >= 3
    assert any(l["decoded"] for l in log
               if l["iter"] < admit_iters[-1]), \
        "A was not mid-decode across the admissions"


def test_short_prefix_under_long_prompt_falls_back_to_cold(
        gpt, decode_model):
    """A resident SHORT prefix must not be reused under a prompt whose
    padded suffix would outgrow the cold layout (q + round_up(suffix)
    > round_up(t0)): past the top bucket that reuse would hard-fail a
    request a cold prefill serves fine, and below it it would balloon
    the whole cache's bucket.  Such prompts take the cold path — same
    tokens as a cache-off engine, no error."""
    from tests.test_generation import _reference_greedy
    rng = onp.random.RandomState(4)
    head = rng.randint(1, 90, (16,)).astype("int32")
    short = onp.concatenate([head, rng.randint(1, 90, (2,))
                             .astype("int32")])       # inserts q=16
    # 16 + round_up(34) = 48 > round_up(50) = 64?  No — pick sizes so
    # q + sb > round_up(t0): t0 = 40 -> round_up = 64; suffix 24 ->
    # sb = 32; 16 + 32 = 48 <= 64 would reuse.  Use t0 = 60: suffix
    # 44 -> sb = 64; 16 + 64 = 80 > round_up(60) = 64 -> must go cold
    long_p = onp.concatenate([head, rng.randint(1, 90, (44,))
                              .astype("int32")])
    want = _reference_greedy(gpt, long_p, 4)
    eng = _engine(decode_model, prefix_slots=4, max_tokens=4)
    s = eng.submit(short, max_new_tokens=2)
    _drain(eng, s)
    s.result(timeout=10)
    h0 = metrics.value("mxnet_gen_prefix_cache_hits_total")
    s2 = eng.submit(long_p, max_new_tokens=4)
    _drain(eng, s2)
    assert s2.result(timeout=10) == want
    assert s2.finish_reason == "length"
    assert metrics.value("mxnet_gen_prefix_cache_hits_total") == h0, \
        "short prefix was reused despite outgrowing the cold layout"


def test_prefix_cache_refcount_and_lru():
    rows = [onp.zeros((8, 2, 4), "f4")]
    pc = PrefixCache(slots=2)
    k1 = prefix_key(onp.arange(8, dtype="int32"), 8)
    k2 = prefix_key(onp.arange(1, 9, dtype="int32"), 8)
    k3 = prefix_key(onp.arange(2, 10, dtype="int32"), 8)
    assert pc.insert(k1, rows, rows, 8)
    assert pc.insert(k2, rows, rows, 8)
    e1 = pc.lookup(k1, pin=True)             # k1 pinned AND freshest
    assert e1 is not None and e1.refs == 1
    ev0 = metrics.value("mxnet_gen_prefix_cache_evictions_total")
    assert pc.insert(k3, rows, rows, 8)      # evicts k2 (LRU, ref 0)
    assert pc.lookup(k2) is None
    assert pc.lookup(k1) is not None, "a pinned entry was evicted"
    assert metrics.value("mxnet_gen_prefix_cache_evictions_total") \
        == ev0 + 1
    # with every entry pinned, insert refuses rather than evict
    pc.lookup(k3, pin=True)
    k4 = prefix_key(onp.arange(3, 11, dtype="int32"), 8)
    assert not pc.insert(k4, rows, rows, 8)
    pc.unpin(k1)
    pc.unpin(k3)
    assert pc.insert(k4, rows, rows, 8)
    d = pc.describe()
    assert d["entries"] == 2 and d["slots"] == 2
    # disabled cache accepts nothing
    off = PrefixCache(slots=0)
    assert not off.insert(k1, rows, rows, 8)
    assert len(off) == 0


def test_recovery_request_carries_sampling(decode_model):
    from mxnet_tpu.serving.generation import (GenRequest,
                                              make_recovery_request)
    req = GenRequest(PROMPT_A, 8, None, None, method="top_p",
                     temperature=1.2, top_k=13, top_p=0.6, seed=99)
    req.stream.put(4, index=0)
    req.stream.put(7, index=1)
    r = make_recovery_request(req)
    assert (r.method, r.temperature, r.top_k, r.top_p, r.seed) \
        == ("top_p", 1.2, 13, 0.6, 99)
    assert r.offset == 2 and r.max_new_tokens == 6
    assert list(r.tokens[-2:]) == [4, 7]


# ---------------------------------------------------------------------------
# HTTP: sampling params, structured 400s on stream AND collect paths
# ---------------------------------------------------------------------------

def test_generate_http_sampling_params_and_400s(decode_model):
    eng = _engine(decode_model, max_slots=2)
    with GenerationServer(eng) as gs:
        httpd = serving.make_http_server(None, port=0,
                                         generation_server=gs)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        host, port = httpd.server_address
        url = f"http://{host}:{port}/v1/generate"

        def post(body):
            req = urllib.request.Request(url,
                                         data=json.dumps(body).encode())
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        try:
            base = {"tokens": [int(t) for t in PROMPT_A],
                    "max_new_tokens": 5, "stream": False,
                    "method": "top_k", "temperature": 0.8,
                    "top_k": 5, "seed": 42}
            out1 = post(base)
            out2 = post(base)
            assert out1["tokens"] == out2["tokens"], \
                "same-seed HTTP requests diverged"
            assert len(out1["tokens"]) == 5
            # out-of-range values: 400 on BOTH paths (the structured
            # error precedes any token either way)
            for stream_mode in (False, True):
                for bad in ({"method": "beam"},
                            {"method": "sample", "temperature": 0},
                            {"method": "top_k", "top_k": 0},
                            {"method": "top_p", "top_p": 0.0},
                            {"method": "top_p", "top_p": 1.5},
                            {"method": 7},
                            {"method": "sample", "seed": "abc"},
                            {"method": "sample", "seed": 2**31},
                            {"method": "sample", "temperature": "x"}):
                    body = dict(base, stream=stream_mode, **bad)
                    with pytest.raises(urllib.error.HTTPError) as he:
                        post(body)
                    assert he.value.code == 400, \
                        f"{bad} on stream={stream_mode} -> " \
                        f"{he.value.code}"
                    detail = json.loads(he.value.read())
                    assert detail["error"] == "bad_request"
        finally:
            httpd.shutdown()
