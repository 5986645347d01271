"""Continuous-batching generation engine (mxnet_tpu/serving/generation.py):
slot/bucket KV cache, greedy parity vs an uncompiled reference loop, the
iteration-level scheduling invariant (mid-flight admission changes no
resident sequence's tokens), EOS/max-token retirement, structured
overload sheds, decode-fault blast radius, per-token HTTP streaming.

ISSUE 6 specifies the cases; the invariant assertions run against the
engine's per-iteration slot logs (`iteration_log`), not just final
outputs.
"""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, metrics, serving
from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                               GenerationServer, OverloadError,
                               PagedKVCache)
from mxnet_tpu.serving.kv_cache import round_up_bucket

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from serial_decode import (StepCounters, check_log,         # noqa: E402
                           run_staggered, serial_transcript)

VOCAB = 97
PROMPT_A = onp.array([5, 9, 3, 17], dtype="int32")
PROMPT_B = onp.array([1, 2], dtype="int32")


@pytest.fixture(scope="module")
def gpt():
    """Tiny decoder LM with a strong init: random-init GPTs collapse to
    one token; Normal(1.0) gives varied, deterministic-greedy output so
    positional bugs can't hide behind a constant sequence."""
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


def _reference_greedy(gpt, prompt, n):
    """The uncompiled reference loop: a full forward over the whole
    sequence per token, host argmax, append — no KV cache, none of the
    engine's programs.  The sequence rides padded to one fixed length
    (causal attention: positions past the real length cannot influence
    the read position), so the reference itself stays one compiled
    shape instead of one per length."""
    PAD = 64
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        padded = toks + [0] * (PAD - len(toks))
        logits = gpt(mx.np.array(
            onp.asarray([padded], "int32"))).asnumpy()
        nxt = int(logits[0, len(toks) - 1].argmax())
        out.append(nxt)
        toks.append(nxt)
    return out


def _engine(decode_model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("kv_buckets", (16, 32, 64))
    kw.setdefault("max_tokens", 48)
    eng = GenerationEngine(decode_model, **kw)
    eng.warmup()
    return eng


def _drain(eng, *streams, max_iters=200):
    it = 0
    while not all(s.finished for s in streams) and it < max_iters:
        eng.run_iteration()
        it += 1
    assert it < max_iters, "engine did not finish the sequences"


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def test_kv_cache_slots_and_buckets():
    c = PagedKVCache(n_layers=2, n_heads=2, head_dim=4, max_slots=3,
                     buckets=(8, 16, 32))
    assert c.bucket == 8 and c.free_slots() == [0, 1, 2]
    s0, s1 = c.alloc(), c.alloc()
    assert (s0, s1) == (0, 1) and c.occupancy() == 2
    c.positions[s0], c.positions[s1] = 5, 7
    assert c.needed_capacity() == 8
    assert not c.ensure_capacity(8)          # fits the current bucket
    assert c.ensure_capacity(9)              # 9 > 8 -> migrate to 16
    assert c.bucket == 16
    assert c.k(0).shape == (3, 2 * 4, 16)           # positions last
    c.free(s0)
    assert c.free_slots() == [0, 2]
    c.free(s1)
    c.reset_if_empty()
    assert c.bucket == 8                     # shrinks only when empty
    assert round_up_bucket(17, (8, 16, 32)) == 32
    with pytest.raises(mx.MXNetError):
        round_up_bucket(33, (8, 16, 32))
    with pytest.raises(mx.MXNetError):
        c.ensure_capacity(40)                # past the top bucket


def _resident_rows(c, slot):
    """A slot's rows as callers hand them in: per layer (L, heads, d)
    K then V, whatever axis order the cache keeps them in."""
    return [onp.asarray(b)[slot].T.reshape(-1, c.n_heads, c.head_dim)
            for b in [c.k(i) for i in range(c.n_layers)]
            + [c.v(i) for i in range(c.n_layers)]]


@pytest.mark.parametrize("grown", [False, True],
                         ids=["same_bucket", "after_grow"])
@pytest.mark.parametrize("start", [0, 3], ids=["at_0", "at_offset"])
def test_kv_write_prompt_round_trip(start, grown):
    """write_prompt takes (Lp, heads, d) rows and the resident buffers
    keep positions LAST: what was written at ``start`` reads back equal
    (and nothing else in the slot moved), before and after a grow."""
    rng = onp.random.RandomState(start + 7 * grown)
    c = PagedKVCache(n_layers=2, n_heads=2, head_dim=4, max_slots=3,
                     buckets=(8, 16))
    assert c.describe()["layout"] == "(max_slots, heads*head_dim, bucket)"
    assert c.k(0).shape == (3, 2 * 4, 8)
    c.alloc()
    slot = c.alloc()
    rows = [rng.randn(5, 2, 4).astype("float32") for _ in range(4)]
    c.write_prompt(slot, rows[:2], rows[2:], start + 5, start=start)
    if grown:
        c.grow(16)
        assert c.v(1).shape == (3, 2 * 4, 16)
    for got, want in zip(_resident_rows(c, slot), rows):
        assert got.shape == (c.bucket, 2, 4)
        onp.testing.assert_array_equal(got[start:start + 5], want)
        assert not got[:start].any() and not got[start + 5:].any()
    for other in (0, 2):                     # no other slot was touched
        assert not any(r.any() for r in _resident_rows(c, other))
    assert c.positions[slot] == start + 5


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, cond, while)
    included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_decode_step_relayouts_no_resident_buffer(decode_model):
    """The decode step must compute in the order the cache stores: no
    ``transpose`` of a whole K/V buffer, and no ``scatter`` into one (a
    TPU scatter wants its update window on the minor axes, so XLA
    relayouts the whole buffer around it, every layer, every token —
    two thirds of a decode step before PERF.md's PR 27).  The on-chip
    form of this guard reads the optimized HLO (chip_smoke.py)."""
    import jax
    S = 3
    c = PagedKVCache(decode_model.n_layers, decode_model.num_heads,
                     decode_model.head_dim, max_slots=S, buckets=(16,))
    resident = sorted(c.k(0).shape)     # in any axis order
    zeros = onp.zeros((S,), "int32")
    jaxpr = jax.make_jaxpr(decode_model._step_fn)(
        decode_model.params, c._k, c._v, zeros, zeros,
        *decode_model.greedy_sampling(S))
    seen = set()
    for e in _eqns(jaxpr.jaxpr):
        seen.add(e.primitive.name)
        if e.primitive.name in ("transpose", "scatter", "scatter-add"):
            assert resident not in [sorted(v.aval.shape)
                                    for v in e.invars], \
                f"{e.primitive.name} over a resident KV buffer: {e}"
    # the walk really went inside the jitted program
    assert {"dynamic_update_slice", "dot_general"} <= seen


def test_chip_smoke_hlo_guard_reads_copies_of_a_kv_buffer():
    """chip_smoke.cache_sized_relayouts on what the v5e's compiler
    gave for gpt2_774m at 8 x 1024 (PR 27): the relayouts around a
    scatter, and the in-place column writes that replaced them."""
    import chip_smoke
    n = 8 * 1024 * 20 * 64
    bad = """\
  %copy.13 = f32[8,1024,20,64]{3,2,1,0:T(8,128)} copy(%ks_0_.1), sharding={replicated}
  ROOT %transpose.2 = f32[8,20,64,1024]{3,2,1,0} transpose(%p.1), dimensions={0,2,3,1}
  %copy.7 = f32[8,20,64]{2,1,0:T(8,128)} copy(%fusion.9)
"""
    good = """\
  %dynamic_update_slice.24 = f32[8,1280,1024]{2,1,0:T(8,128)} dynamic-update-slice(%vs_0_.1, %squeeze.0, %c.1, %c.1, %select_n.38)
  %copy-start.2 = (f32[8,1280,1024]{2,1,0:T(8,128)S(1)}, f32[8,1280,1024]{2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%dynamic_update_slice.31)
  %copy-done.2 = f32[8,1280,1024]{2,1,0:T(8,128)S(1)} copy-done(%copy-start.2)
  %copy.15 = f32[1,1280,1]{2,1,0:T(8,128)} copy(%bitcast.4)
"""
    assert [l.split(" = ")[0] for l in
            chip_smoke.cache_sized_relayouts(bad, n)] \
        == ["%copy.13", "ROOT %transpose.2"]
    assert chip_smoke.cache_sized_relayouts(good, n) == []


# ---------------------------------------------------------------------------
# greedy parity (incl. a KV-bucket migration mid-decode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("buckets, new_tokens, migrations", [
    # 24 new tokens from a 4-token prompt crosses the 16-bucket: the
    # parity window covers prefill, steady decode, AND a live cache
    # migration
    pytest.param((16, 32, 64), 24, 1, id="16_to_32", marks=pytest.mark.slow),  # tier-1 time budget (r8): generation-smoke gates greedy parity end-to-end in tier 1
    # one request grows through EVERY bucket of the grid: positions
    # written before a grow stay where the next bucket's step reads them
    pytest.param((8, 16, 32), 26, 2, id="through_8_16_32"),
])
def test_greedy_parity_vs_uncompiled_reference(gpt, decode_model, buckets,
                                               new_tokens, migrations):
    eng = _engine(decode_model, kv_buckets=buckets)
    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    s = eng.submit(PROMPT_A, max_new_tokens=new_tokens)
    _drain(eng, s)
    got = s.result(timeout=10)
    assert got == _reference_greedy(gpt, PROMPT_A, new_tokens)
    assert s.finish_reason == "length"
    assert metrics.value("mxnet_gen_kv_migrations_total") \
        == m0 + migrations


def test_decode_zero_compiles_after_warmup(gpt, decode_model):
    eng = _engine(decode_model)
    # one full traffic wave to settle anything first-use
    _drain(eng, eng.submit(PROMPT_A, max_new_tokens=4))
    c0 = metrics.value("mxnet_compile_misses_total")
    streams = [eng.submit(p, max_new_tokens=6) for p in
               (PROMPT_A, PROMPT_B, onp.arange(1, 8, dtype="int32"))]
    _drain(eng, *streams)
    assert all(len(s.result(timeout=10)) == 6 for s in streams)
    assert metrics.value("mxnet_compile_misses_total") == c0, \
        "steady-state decode recompiled"


# ---------------------------------------------------------------------------
# the continuous-batching invariant
# ---------------------------------------------------------------------------

def test_midflight_admission_changes_no_resident_tokens(gpt,
                                                        decode_model):
    want_a = _reference_greedy(gpt, PROMPT_A, 20)
    want_b = _reference_greedy(gpt, PROMPT_B, 10)
    eng = _engine(decode_model)
    sa = eng.submit(PROMPT_A, max_new_tokens=20)
    for _ in range(6):                       # A is mid-decode...
        eng.run_iteration()
    sb = eng.submit(PROMPT_B, max_new_tokens=10)   # ...when B arrives
    _drain(eng, sa, sb)
    # neither sequence's tokens moved for the other
    assert sa.result(timeout=10) == want_a
    assert sb.result(timeout=10) == want_b
    # per-iteration slot logs prove B was admitted while A was decoding
    # and the two then shared iterations
    log = list(eng.iteration_log)
    b_admit = next(l["iter"] for l in log[1:] if l["admitted"])
    assert any(l["decoded"] for l in log if l["iter"] < b_admit), \
        "A was not mid-decode at B's admission"
    assert sum(1 for l in log if len(l["decoded"]) == 2) >= 5, \
        "A and B never actually decoded in the same iterations"


# ---------------------------------------------------------------------------
# retirement
# ---------------------------------------------------------------------------

def test_eos_and_max_token_retirement_free_slots(gpt, decode_model):
    base = _reference_greedy(gpt, PROMPT_A, 12)
    assert len(set(base)) > 1, "degenerate fixture: constant sequence"
    eos = base[3]
    stop_at = base.index(eos)                # its FIRST occurrence
    eng = _engine(decode_model, max_slots=1)
    s = eng.submit(PROMPT_A, max_new_tokens=12, eos_token=eos)
    _drain(eng, s)
    got = s.result(timeout=10)
    assert s.finish_reason == "eos"
    assert got == base[:stop_at + 1]         # stops AT the eos token
    # the slot frees at the next iteration's retire phase
    eng.run_iteration()
    assert eng.cache.free_slots() == [0]
    s2 = eng.submit(PROMPT_B, max_new_tokens=3)
    _drain(eng, s2)
    assert s2.finish_reason == "length"      # max-token retirement
    assert len(s2.result(timeout=10)) == 3
    eng.run_iteration()
    assert eng.cache.free_slots() == [0]
    assert metrics.value("mxnet_gen_retirements_total",
                         reason="eos") >= 1
    assert metrics.value("mxnet_gen_retirements_total",
                         reason="length") >= 1


# ---------------------------------------------------------------------------
# one step in flight: the loop launches step N+1 before it reads step N
# ---------------------------------------------------------------------------

# mixed lengths over two slots: two arrive together, two more while
# those decode (each waits for a slot), the last into a slot that has
# stood free; the long one grows the rows twice with a step in flight.
# Admissions and finishes fall mid-run and most other quanta run ahead
STAGGERED = [
    {"prompt": PROMPT_A, "max_new_tokens": 30, "at": 0},
    {"prompt": PROMPT_B, "max_new_tokens": 5, "at": 0},
    {"prompt": onp.arange(1, 8, dtype="int32"), "max_new_tokens": 9,
     "at": 3},
    {"prompt": onp.array([11, 3, 8], "int32"), "max_new_tokens": 3,
     "at": 6},
    {"prompt": onp.array([2, 40, 7, 7, 19], "int32"), "max_new_tokens": 11,
     "at": 22},
]


def test_transcripts_equal_the_serial_step_loop(decode_model):
    """Greedy: every stream's tokens and finish reason equal the same
    request decoded alone by ``DecodeModel.step``, launch-wait-read; the
    run really ran ahead, and really fell back for its admissions and
    finishes.  (Sampled lanes: test_gen_sampling; the hybrid family:
    test_phi4flash.)"""
    eng = _engine(decode_model)
    counted = StepCounters()
    m0 = metrics.value("mxnet_gen_kv_migrations_total")
    streams = run_staggered(eng, STAGGERED)
    assert metrics.value("mxnet_gen_kv_migrations_total") == m0 + 2
    for s, r in zip(streams, STAGGERED):
        want = serial_transcript(decode_model, eng, r["prompt"],
                                 r["max_new_tokens"])
        assert (s.result(timeout=5), s.finish_reason) == want
    moved = counted.moved()
    assert moved["ahead"] >= 25
    assert moved["finish"] >= 2 and moved["admit"] >= 1 \
        and moved["idle"] >= 1
    assert moved["discarded"] == 0          # nothing ended on an EOS
    check_log(eng, streams)


def test_every_step_is_counted_ahead_or_fallen_back(decode_model, traced):
    """``steps_ahead`` + the fall-backs count every decode step, which
    is every quantum that emitted a step's tokens; every
    ``model.step.dispatch`` span says which it was."""
    eng = _engine(decode_model)
    traced.reset()
    counted = StepCounters()
    steps0 = metrics.hist_stats("mxnet_gen_step_seconds", phase="decode")[1]
    streams = run_staggered(eng, STAGGERED)
    moved = counted.moved()
    assert moved["ahead"] + counted.fallbacks() == moved["iterations"]
    assert moved["iterations"] == sum(
        1 for e in eng.iteration_log if e["decoded"])
    launches = [r for r in traced.spans()
                if r["name"] == "model.step.dispatch"]
    assert len(launches) == moved["iterations"]
    assert all(r["attrs"]["ahead"] in (0, 1) for r in launches)
    assert sum(r["attrs"]["ahead"] for r in launches) == moved["ahead"]
    # what the token counters say is what the clients got
    delivered = sum(len(s.result(timeout=5)) for s in streams)
    assert moved["decode_tokens"] + moved["prefill_tokens"] == delivered
    assert moved["sampled"] == delivered
    # one observation of its cost a step, and none left in flight
    assert metrics.hist_stats("mxnet_gen_step_seconds",
                              phase="decode")[1] - steps0 \
        == moved["iterations"]
    assert eng._flight is None


def test_eos_is_read_one_step_late_and_nothing_leaks(decode_model):
    """With EOS at step N the host learns of it after N+1 was launched:
    N+1's token for that slot reaches no stream and no token counter,
    the stream ends AT the eos, and the slot's next owner decodes as if
    alone."""
    eng = _engine(decode_model, max_slots=1)
    base, _ = serial_transcript(decode_model, eng, PROMPT_A, 12)
    eos = base[3]
    cut = base[:base.index(eos) + 1]
    assert 2 <= len(cut) < 10, "fixture: the eos must fall mid-decode"
    counted = StepCounters()
    sa = eng.submit(PROMPT_A, max_new_tokens=12, eos_token=eos)
    sb = eng.submit(PROMPT_B, max_new_tokens=6)      # waits for the slot
    _drain(eng, sa, sb)
    assert not eng.run_iteration()
    assert (sa.result(timeout=5), sa.finish_reason) == (cut, "eos")
    assert (sb.result(timeout=5), sb.finish_reason) \
        == serial_transcript(decode_model, eng, PROMPT_B, 6)
    moved = counted.moved()
    assert moved["discarded"] == 1
    delivered = len(cut) + 6
    assert moved["decode_tokens"] + moved["prefill_tokens"] == delivered
    assert moved["sampled"] == delivered
    check_log(eng, [sa, sb])
    assert eng.cache.free_slots() == [0]


def test_cancel_with_a_step_in_flight(decode_model):
    """A consumer gives up between two quanta, with a step launched
    over its slot: the next quantum falls back, the token in flight is
    discarded, the slot frees, and neither its neighbour nor the slot's
    next owner sees any of it."""
    eng = _engine(decode_model)
    sa = eng.submit(PROMPT_A, max_new_tokens=30)
    sb = eng.submit(onp.arange(1, 8, dtype="int32"), max_new_tokens=12)
    for _ in range(4):
        eng.run_iteration()
    assert eng._flight is not None
    counted = StepCounters()
    had = list(sa.tokens)
    sa.cancel()
    eng.run_iteration()
    moved = counted.moved()
    assert moved["cancel"] == 1 and moved["ahead"] == 0
    assert moved["discarded"] == 1
    assert sa.tokens == had and sa.finished
    assert eng.cache.free_slots() == [0]
    assert metrics.value("mxnet_gen_retirements_total",
                         reason="cancelled") >= 1
    sc = eng.submit(PROMPT_B, max_new_tokens=5)      # takes the freed slot
    _drain(eng, sb, sc)
    assert sb.result(timeout=5) == serial_transcript(
        decode_model, eng, onp.arange(1, 8, dtype="int32"), 12)[0]
    assert sc.result(timeout=5) == serial_transcript(
        decode_model, eng, PROMPT_B, 5)[0]


# ---------------------------------------------------------------------------
# overload
# ---------------------------------------------------------------------------

def test_shed_paths_raise_structured_overload(decode_model):
    eng = _engine(decode_model, max_slots=1, queue_limit=2)
    # fill the slot and the bounded admission queue
    s1 = eng.submit(PROMPT_A, max_new_tokens=40)
    eng.run_iteration()                      # s1 occupies the slot
    eng.submit(PROMPT_B, max_new_tokens=4)
    eng.submit(PROMPT_B, max_new_tokens=4)
    with pytest.raises(OverloadError) as ei:
        eng.submit(PROMPT_B, max_new_tokens=4)
    assert ei.value.reason == "queue_full"
    j = ei.value.to_json()
    assert j["error"] == "overloaded" and j["queue_depth"] >= 2 \
        and "retry_after_ms" in j
    # deadline shed: no slot frees within the request's deadline
    eng2 = _engine(decode_model, max_slots=1, queue_limit=4)
    sa = eng2.submit(PROMPT_A, max_new_tokens=40)
    eng2.run_iteration()
    sb = eng2.submit(PROMPT_B, max_new_tokens=4, deadline_ms=5.0)
    time.sleep(0.02)                         # deadline passes queued
    eng2.run_iteration()                     # admission boundary sheds
    with pytest.raises(OverloadError) as ei2:
        sb.result(timeout=5)
    assert ei2.value.reason == "deadline"
    assert not sa.finished                   # the resident one decodes on


# ---------------------------------------------------------------------------
# fault blast radius (PR-3 plan grammar at the serving.execute site)
# ---------------------------------------------------------------------------

def test_decode_fault_fails_only_affected_sequences(gpt, decode_model):
    want_b = _reference_greedy(gpt, PROMPT_B, 5)
    eng = _engine(decode_model, max_slots=1)
    # site hit #1 is A's prefill, #2/#3 its first decode iterations;
    # after=3:times=1 detonates ONE decode step while A holds the slot
    with faults.fault_plan("serving.execute:after=3:times=1"):
        sa = eng.submit(PROMPT_A, max_new_tokens=30)
        sb = eng.submit(PROMPT_B, max_new_tokens=5)   # queued behind A
        _drain(eng, sa, sb)
    with pytest.raises(mx.MXNetError, match="injected"):
        sa.result(timeout=5)
    assert sa.finish_reason == "error"
    # the queued sequence admitted after the blast and decoded clean
    assert sb.result(timeout=10) == want_b
    assert sb.finish_reason == "length"
    # the engine survived: a fresh request still serves
    s3 = eng.submit(PROMPT_A, max_new_tokens=3)
    _drain(eng, s3)
    assert len(s3.result(timeout=10)) == 3


# ---------------------------------------------------------------------------
# server thread + HTTP streaming
# ---------------------------------------------------------------------------

def test_generation_server_http_stream_and_errors(decode_model):
    eng = _engine(decode_model, max_slots=2)
    with GenerationServer(eng) as gs:
        httpd = serving.make_http_server(None, port=0,
                                         generation_server=gs)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        host, port = httpd.server_address
        try:
            # per-token streaming is OBSERVABLE: read the raw chunked
            # wire and require at least one token line to arrive before
            # the done trailer
            body = json.dumps({"tokens": [int(t) for t in PROMPT_A],
                               "max_new_tokens": 5}).encode()
            with socket.create_connection((host, port),
                                          timeout=30) as sk:
                sk.sendall(
                    b"POST /v1/generate HTTP/1.1\r\n"
                    + f"Host: {host}\r\n".encode()
                    + f"Content-Length: {len(body)}\r\n".encode()
                    + b"Content-Type: application/json\r\n\r\n" + body)
                raw = b""
                sk.settimeout(30)
                while b"\"done\": true" not in raw:
                    chunk = sk.recv(4096)
                    assert chunk, "connection closed before trailer"
                    raw += chunk
            head, _, payload = raw.partition(b"\r\n\r\n")
            assert b"200" in head.split(b"\r\n", 1)[0]
            assert b"chunked" in head.lower()
            lines = [json.loads(l) for l in payload.decode()
                     .replace("\r\n", "\n").split("\n")
                     if l.strip().startswith("{")]
            toks = [l["token"] for l in lines if "token" in l]
            assert len(toks) == 5
            assert lines[-1]["done"] and \
                lines[-1]["finish_reason"] == "length"
            # non-stream mode
            req = urllib.request.Request(
                f"http://{host}:{port}/v1/generate",
                data=json.dumps({"tokens": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "stream": False}).encode())
            with urllib.request.urlopen(req, timeout=30) as r:
                out = json.loads(r.read())
            assert len(out["tokens"]) == 4
            assert out["finish_reason"] == "length"
            # malformed -> 400; an over-long PROMPT (past the KV/
            # position ceiling; max_new_tokens is merely clamped) -> 400
            for bad in ({"tokens": []},
                        {"tokens": [1] * 100, "max_new_tokens": 4}):
                req = urllib.request.Request(
                    f"http://{host}:{port}/v1/generate",
                    data=json.dumps(bad).encode())
                with pytest.raises(urllib.error.HTTPError) as he:
                    urllib.request.urlopen(req, timeout=30)
                assert he.value.code == 400
            # healthz reports generation slots
            with urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=10) as r:
                h = json.loads(r.read())
            assert h["status"] == "ok"
            assert h["generation"]["slots"]["max"] == 2
        finally:
            httpd.shutdown()
    # stopped server refuses with a structured state error
    with pytest.raises(mx.MXNetError):
        gs.generate([1, 2])


def test_generation_server_shutdown_fails_inflight(decode_model):
    eng = _engine(decode_model, max_slots=1)
    gs = GenerationServer(eng).start()
    s = gs.generate(PROMPT_A, max_new_tokens=40)
    t0 = time.monotonic()
    while s.tokens == [] and time.monotonic() - t0 < 10:
        time.sleep(0.005)                    # admitted and decoding
    gs.stop()
    with pytest.raises(mx.MXNetError, match="shutdown|stopped"):
        # drain whatever streamed, then observe the structured error
        while s.next_token(timeout=5) is not None:
            pass


# ---------------------------------------------------------------------------
# the engine's own spans (ISSUE 26)
# ---------------------------------------------------------------------------

@pytest.fixture
def traced():
    from mxnet_tpu import tracing
    tracing.configure(sample=1.0)
    yield tracing
    tracing.configure()


def _inside(child, parent):
    return parent["t_begin"] <= child["t_begin"] \
        and child["t_end"] <= parent["t_end"]


def test_in_process_submit_gets_a_trace_of_its_own(decode_model, traced):
    """engine.submit() under no caller trace: one trace holds queue.wait
    and engine.prefill, and under the latter model.prefill,
    kv.write_prompt and model.select."""
    eng = _engine(decode_model)
    traced.reset()
    assert traced.current_context() is None
    n0 = metrics.hist_stats("mxnet_gen_queue_wait_seconds")[1]
    # the histogram keeps its longest recent wait's exemplar: forget
    # the ones earlier tests left
    metrics.GEN_QUEUE_WAIT_SECONDS._default().exemplar = None
    s = eng.submit(PROMPT_A, max_new_tokens=4)
    _drain(eng, s)
    prefill, = [r for r in traced.spans()
                if r["name"] == "engine.prefill"]
    trace = traced.spans(prefill["trace_id"])
    by = {r["name"]: r for r in trace}
    assert set(by) == {"queue.wait", "engine.prefill", "model.prefill",
                       "kv.write_prompt", "model.select"}, sorted(by)
    assert by["queue.wait"]["parent_id"] == prefill["parent_id"]
    for name in ("model.prefill", "kv.write_prompt", "model.select"):
        assert by[name]["parent_id"] == prefill["span_id"], name
        assert _inside(by[name], prefill), name
    assert by["queue.wait"]["t_end"] <= prefill["t_begin"]
    assert by["model.prefill"]["attrs"]["bucket"] == 8
    assert by["kv.write_prompt"]["attrs"]["rows"] == 8
    # one observation of the queue wait an admission, with the
    # request's trace as its exemplar
    assert metrics.hist_stats(
        "mxnet_gen_queue_wait_seconds")[1] == n0 + 1
    assert metrics.GEN_QUEUE_WAIT_SECONDS._default().exemplar[0] \
        == prefill["trace_id"]
    # the iteration links the request's trace, as it does over HTTP
    assert any(prefill["trace_id"] in r.get("links", ())
               for r in traced.spans() if r["name"] == "engine.iteration")


def test_iteration_span_covers_its_whole_quantum(decode_model, traced):
    """By time, on one thread, engine.iteration contains every
    engine.prefill, model.step.dispatch, model.step.readback and
    engine.emit of its quantum.  A quantum that runs ahead launches the
    next step (``ahead=1``) BEFORE it reads the last one back; one that
    falls back reads first and launches last (``ahead=0``)."""
    eng = _engine(decode_model)
    traced.reset()
    a = eng.submit(PROMPT_A, max_new_tokens=6)
    eng.run_iteration()
    b = eng.submit(PROMPT_B, max_new_tokens=3)    # admitted mid-flight
    _drain(eng, a, b)
    # the last sequence retired in the quantum that read its last token
    assert eng.cache.free_slots() == [0, 1]
    assert not eng.run_iteration()  # an idle pass is a (short) span too
    recs = traced.spans()
    iters = [r for r in recs if r["name"] == "engine.iteration"]
    assert len({r["tid"] for r in recs}) == 1
    assert len(iters) == len({r["trace_id"] for r in iters})
    inside = {}
    for name in ("engine.prefill", "model.step.dispatch",
                 "model.step.readback", "engine.emit"):
        found = [r for r in recs if r["name"] == name]
        assert found, name
        for r in found:
            homes = [i for i in iters if _inside(r, i)]
            assert len(homes) == 1, (name, len(homes))
            inside.setdefault(homes[0]["span_id"], []).append(r)
    assert not [r for r in recs if r["name"] == "model.step"]
    # every iteration that emitted has exactly one readback and one
    # emit; every step launched was read (nothing is left in flight)
    emitted = [i for i in iters if i["attrs"]["tokens"]]
    for name in ("model.step.readback", "engine.emit",
                 "model.step.dispatch"):
        assert sum(r["name"] == name for r in recs) == len(emitted), name
    assert len([r for r in recs if r["name"] == "engine.prefill"]) == 2
    first = iters[0]["attrs"]
    # the first quantum admits and launches; its step is read next time
    assert (first["admitted"], first["slots"], first["tokens"]) == (1, 1, 0)
    assert sum(i["attrs"]["tokens"] for i in iters) == (6 - 1) + (3 - 1)
    assert sum(i["attrs"]["retired"] for i in iters) == 2
    assert iters[-1]["attrs"] == {"iter": iters[-1]["attrs"]["iter"],
                                  "slots": 0, "admitted": 0, "retired": 0,
                                  "tokens": 0}
    by_id = {r["span_id"]: r for r in recs}
    aheads = []
    for it in iters:
        parts = sorted(inside.get(it["span_id"], ()),
                       key=lambda r: r["t_begin"])
        names = [p["name"] for p in parts if p["name"] != "engine.prefill"]
        launch = [p for p in parts if p["name"] == "model.step.dispatch"]
        for p in parts:
            if p["name"] != "engine.prefill":   # that one is the request's
                assert by_id[p["parent_id"]]["name"] == "engine.iteration"
        if not launch:
            assert names in ([], ["model.step.readback", "engine.emit"])
            continue
        assert launch[0]["attrs"] == {
            "slots": 2, "bucket": launch[0]["attrs"]["bucket"],
            "family": "gpt", "ahead": launch[0]["attrs"]["ahead"]}
        aheads.append(launch[0]["attrs"]["ahead"])
        if aheads[-1]:
            assert names == ["model.step.dispatch", "model.step.readback",
                             "engine.emit"]
        else:
            assert names in (["model.step.dispatch"],
                             ["model.step.readback", "engine.emit",
                              "model.step.dispatch"])
    # a (6 tokens) and b (3): the first launch and the one after b's
    # admission are serial, b's and a's last tokens end their steps'
    # successors: some of each kind
    assert 0 in aheads and 1 in aheads
    emits = [r for r in recs if r["name"] == "engine.emit"]
    assert [e["attrs"]["tokens"] for e in emits] \
        == [i["attrs"]["tokens"] for i in iters if i["attrs"]["tokens"]]


def test_tracing_off_constructs_nothing_at_the_engines_sites(
        decode_model, traced, monkeypatch):
    traced.configure(sample=0)
    made = []
    monkeypatch.setattr(traced, "_Span", lambda *a, **k: made.append(a))
    monkeypatch.setattr(traced, "_TraceState",
                        lambda *a, **k: made.append(a))
    eng = _engine(decode_model)
    s = eng.submit(PROMPT_A, max_new_tokens=4)
    _drain(eng, s)
    assert len(s.result(timeout=10)) == 4
    assert made == [] and traced.spans() == []
