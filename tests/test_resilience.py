"""Serving resilience (ISSUE 7): replicated workers, exactly-once
stream recovery, circuit breaker, graceful drain.

The invariants under test:

* a dead worker is a ROUTINE event: its work requeues/recovers onto
  healthy replicas and the supervisor restarts it with backoff;
* recovered generation streams are TOKEN-IDENTICAL to a fault-free
  greedy run (deterministic re-prefill of prompt+emitted + TokenStream
  index dedupe = exactly-once on the wire);
* a crash-loop trips the circuit breaker into explicit degraded mode
  (structured DegradedError; readiness 503, liveness 200) and a manual
  reset re-admits traffic;
* SIGTERM drains: admissions shed 429 (never a connection reset),
  resident sequences finish inside MXNET_SERVING_DRAIN_DEADLINE_S,
  exit code 0.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, metrics, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (BucketPolicy, DecodeModel, DegradedError,
                               GenerationEngine, GenerationServer,
                               ModelServer, OverloadError)

VOCAB = 97
PROMPT_A = onp.array([5, 9, 3, 17], dtype="int32")
PROMPT_B = onp.array([1, 2], dtype="int32")
PROMPT_C = onp.array([7, 4, 11], dtype="int32")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from serial_decode import StepCounters, serial_transcript   # noqa: E402


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt():
    """Tiny decoder LM, strong init (same rationale as
    tests/test_generation.py: varied deterministic-greedy output)."""
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
    """Uncompiled full-forward-per-token reference (the ground truth a
    recovered stream must match)."""
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


def _model_server(**kw):
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((1, 6), dtype="float32"))
    model = serving.load_served(net)
    kw.setdefault("policy", BucketPolicy(batch_buckets=(1, 2)))
    kw.setdefault("timeout_ms", 1.0)
    kw.setdefault("restart_backoff_ms", 10.0)
    return ModelServer(model, **kw)


def _wait(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# ModelServer: transient worker death -> requeue + restart, no caller error
# ---------------------------------------------------------------------------

def test_worker_death_requeues_batch_and_restarts():
    restarts0 = metrics.value("mxnet_serving_worker_restarts_total",
                              server="oneshot")
    srv = _model_server().start()
    try:
        x = onp.ones(6, "f4")
        with faults.fault_plan("serving.worker:times=1"):
            # the worker dies holding this request's batch; it must
            # requeue and complete on the restarted worker — the CALLER
            # sees a result, not an error
            out = srv.infer(x, timeout=20.0)
        assert out.shape == (3,)
        assert metrics.value("mxnet_serving_worker_restarts_total",
                             server="oneshot") == restarts0 + 1
        _wait(srv.healthy, what="server healthy after restart")
        assert not srv.degraded
        # and it keeps serving
        assert srv.infer(x, timeout=20.0).shape == (3,)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# crash loop -> breaker -> readiness 503 / liveness 200 -> manual reset
# ---------------------------------------------------------------------------

def test_crash_loop_trips_breaker_reset_readmits():
    from mxnet_tpu.serving.http import make_http_server
    srv = _model_server(max_restarts=2)
    srv.start()
    httpd = make_http_server(srv, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    host, port = httpd.server_address
    x = onp.ones(6, "f4")
    try:
        with faults.fault_plan("serving.worker:p=1"):
            fut = srv.infer_async(x)
            # every restart re-crashes at the site: after
            # max_restarts=2 the breaker must trip
            _wait(lambda: srv.degraded, what="breaker trip")
            with pytest.raises(MXNetError,
                               match="worker thread died.*degraded"):
                fut.result(timeout=10)
            # structured refusal, not a queue-forever
            with pytest.raises(DegradedError, match="degraded"):
                srv.infer_async(x)
            assert metrics.value("mxnet_serving_breaker_open",
                                 server="oneshot") == 1
            # readiness 503, liveness 200 — the orchestrator must NOT
            # kill the pod, the balancer must route away
            try:
                urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=10)
                raise AssertionError("readiness should be 503")
            except urllib.error.HTTPError as e:
                assert e.code == 503
                assert json.loads(e.read())["status"] == "degraded"
            with urllib.request.urlopen(
                    f"http://{host}:{port}/livez", timeout=10) as r:
                live = json.loads(r.read())
            assert live["status"] == "alive" and live["degraded"]
        # cause gone (plan disarmed): the operator resets the breaker
        # and traffic re-admits through the same server object
        srv.reset_breaker()
        assert srv.infer(x, timeout=20.0).shape == (3,)
        assert srv.healthy()
        assert metrics.value("mxnet_serving_breaker_open",
                             server="oneshot") == 0
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        httpd.shutdown()
        srv.stop()


# ---------------------------------------------------------------------------
# exactly-once recovery: decode fault mid-stream, token-identical resume
# ---------------------------------------------------------------------------

@pytest.mark.slow    # tier-1 time budget (r8): resilience-smoke gates token-identical recovery in tier 1
def test_decode_fault_recovers_token_identical(gpt, decode_model):
    want = _reference_greedy(gpt, PROMPT_A, 16)
    rec0 = metrics.value("mxnet_serving_recoveries_total", site="decode")
    eng = _engine(decode_model, max_slots=1)
    with GenerationServer(eng) as gs:
        # site hits: #1 prefill, #2.. decode iterations; after=3:times=1
        # detonates one decode step mid-stream (a few tokens emitted)
        with faults.fault_plan("serving.execute:after=3:times=1"):
            s = gs.generate(PROMPT_A, max_new_tokens=16)
            got = s.result(timeout=30)
        assert got == want, "recovered stream diverged from the " \
            "fault-free greedy run"
        assert s.finish_reason == "length"
    assert metrics.value("mxnet_serving_recoveries_total",
                         site="decode") == rec0 + 1
    assert metrics.value("mxnet_serving_recovered_tokens_total") > 0
    # the engine survived a decode fault WITHOUT a worker restart
    assert faults.injected_count("serving.execute") == 0  # plan left scope


@pytest.mark.slow    # tier-1 time budget (r8): resilience-smoke gates worker-death recovery in tier 1
def test_worker_death_recovers_on_surviving_replica(gpt, decode_model):
    prompts = [PROMPT_A, PROMPT_B, PROMPT_C, PROMPT_A]
    budgets = [14, 10, 12, 8]
    wants = [_reference_greedy(gpt, p, n)
             for p, n in zip(prompts, budgets)]
    factory = lambda: _engine(decode_model, max_slots=2)  # noqa: E731
    rec0 = (metrics.value("mxnet_serving_recoveries_total", site="worker")
            + metrics.value("mxnet_serving_recoveries_total",
                            site="queue"))
    gs = GenerationServer(engine_factory=factory, replicas=2,
                          restart_backoff_ms=10)
    gs.start()
    try:
        # the third busy worker pass dies (whichever replica gets
        # there), with sequences resident and/or queued — all of them
        # must complete token-identical on the survivors
        with faults.fault_plan("serving.worker:after=2:times=1"):
            streams = [gs.generate(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            results = [s.result(timeout=60) for s in streams]
        for got, want, s in zip(results, wants, streams):
            assert got == want, "stream diverged after worker death"
            assert s.finish_reason == "length"
        assert faults.injected_count("serving.worker") == 0  # left scope
        recs = (metrics.value("mxnet_serving_recoveries_total",
                              site="worker")
                + metrics.value("mxnet_serving_recoveries_total",
                                site="queue"))
        assert recs > rec0, "the kill recovered nothing (did it fire?)"
    finally:
        gs.stop()


@pytest.mark.parametrize("moment", ["running_ahead", "at_a_fall_back"])
def test_fault_at_the_late_readback_hits_exactly_the_steps_in_flight(
        decode_model, moment):
    """A fault at ``serving.execute`` surfaces where the host waits for
    a step's tokens.  Running ahead, step N+1 is already launched then:
    the victims are exactly the sequences of the steps in flight (not
    the queued one), neither step's tokens reached a stream, the step
    in flight is dropped, and the recovery replays from the transcripts
    exactly once: every final stream equals the fault-free one."""
    from mxnet_tpu.serving.generation import make_recovery_request
    eng = _engine(decode_model, max_slots=2)
    asks = [(PROMPT_A, 20), (PROMPT_B, 16), (PROMPT_C, 6)]
    wants = [serial_transcript(decode_model, eng, p, n) for p, n in asks]
    hit = []
    eng.recovery_sink = lambda victims, exc, site: hit.append(
        (list(victims), exc, site))
    streams = [eng.submit(p, max_new_tokens=n) for p, n in asks]
    for _ in range(5):
        eng.run_iteration()
    if moment == "at_a_fall_back":
        # B's budget ends with the token in flight, which the host
        # counts itself: the next quantum reads before it launches
        for req in eng.scheduler.active().values():
            if req.stream is streams[1]:
                req.max_new_tokens = req.emitted + 1
    assert eng._flight is not None and len(eng.scheduler) == 1
    had = [list(s.tokens) for s in streams]
    counted = StepCounters()
    dupes0 = metrics.value("mxnet_serving_stream_dupes_dropped_total")
    with faults.fault_plan("serving.execute:p=1:times=1"):
        assert eng.run_iteration()
    moved = counted.moved()
    # the step after N was launched before the fault surfaced, or not
    assert moved["ahead"] == (1 if moment == "running_ahead" else 0)
    assert moved["iterations"] == 0
    (victims, exc, site), = hit
    assert site == "decode" and "injected" in str(exc)
    assert [v.stream for v in victims] == streams[:2]
    assert [list(s.tokens) for s in streams] == had      # nothing leaked
    assert eng._flight is None
    assert eng.cache.free_slots() == [0, 1] and len(eng.scheduler) == 1
    assert not any(s.finished for s in streams)
    # resurrect from the transcripts, as GenerationServer._recover does
    for v in victims:
        eng.submit_request(make_recovery_request(v), front=True)
    while eng.run_iteration():
        pass
    assert [(s.result(timeout=5), s.finish_reason) for s in streams] \
        == wants
    # the first step after it is launched from the host, nothing ahead
    assert counted.moved()["idle"] == 1
    assert metrics.value("mxnet_serving_stream_dupes_dropped_total") \
        == dupes0


def test_recovery_budget_exhausted_fails_structurally(decode_model):
    """A sequence that keeps crashing its decode step must eventually
    FAIL with the underlying error (bounded resurrection), not bounce
    through recovery forever."""
    from mxnet_tpu.serving.generation import GenRequest
    eng = _engine(decode_model, max_slots=1)
    gs = GenerationServer(eng).start()
    try:
        req = GenRequest(PROMPT_A, 8, None, None)
        req.stream.put(5, index=0)               # one emitted token
        req.recoveries = gs.supervisor.max_restarts
        gs._recover([req], MXNetError("boom"), "decode")
        with pytest.raises(MXNetError, match="recovery budget"):
            req.stream.result(timeout=5)
    finally:
        gs.stop()


# ---------------------------------------------------------------------------
# queued-request cancellation frees budget immediately
# ---------------------------------------------------------------------------

def test_queued_cancel_frees_queue_budget_immediately(decode_model):
    eng = _engine(decode_model, max_slots=1, queue_limit=1)
    s1 = eng.submit(PROMPT_A, max_new_tokens=40)
    eng.run_iteration()                      # s1 occupies the only slot
    s2 = eng.submit(PROMPT_B, max_new_tokens=4)
    with pytest.raises(OverloadError):       # queue full
        eng.submit(PROMPT_C, max_new_tokens=4)
    s2.cancel()
    # eviction happens AT cancel, not at the next admission pass: the
    # budget is free with no iteration in between
    assert len(eng.scheduler) == 0
    s4 = eng.submit(PROMPT_C, max_new_tokens=4)
    assert not s4.finished                   # accepted, not shed
    assert not s1.finished                   # resident seq untouched
    eng.close()


# ---------------------------------------------------------------------------
# graceful drain (in-process semantics; SIGTERM e2e below + CI gate)
# ---------------------------------------------------------------------------

def test_generation_drain_finishes_resident_sheds_new(decode_model):
    eng = _engine(decode_model, max_slots=2)
    gs = GenerationServer(eng).start()
    s = gs.generate(PROMPT_A, max_new_tokens=20)
    assert s.next_token(timeout=10) is not None   # resident + streaming
    gs.start_drain()
    assert not gs.ready()                    # out of rotation...
    with pytest.raises(OverloadError) as ei:
        gs.generate(PROMPT_B, max_new_tokens=4)
    assert ei.value.reason == "draining"     # ...and sheds structurally
    rest = [t for t in s]                    # the resident one finishes
    assert len(rest) == 19 and s.finish_reason == "length"
    assert gs.await_drained(timeout=10)
    gs.stop()


def test_model_server_drain_sheds_structurally():
    srv = _model_server().start()
    x = onp.ones(6, "f4")
    try:
        assert srv.infer(x, timeout=20.0).shape == (3,)
        srv.start_drain()
        assert not srv.ready()
        with pytest.raises(OverloadError) as ei:
            srv.infer(x)
        assert ei.value.reason == "draining"
        assert srv.await_drained(timeout=10)
    finally:
        srv.stop()


@pytest.mark.slow
def test_sigterm_drains_and_exits_zero(tmp_path):
    """E2E rolling-restart contract: SIGTERM under streaming load ->
    resident streams finish, new admissions shed 429 (no connection
    reset), readiness 503 / liveness 200 during the window, exit 0.

    Slow-marked (subprocess boot + drain ~15s): the tier-1 wall budget
    is tight, and ``ci/run.sh resilience-smoke`` gates the same
    contract (with 8 clients) on every tier-1 CI run; the in-process
    drain tests above stay in the fast selection."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_SERVING_DRAIN_DEADLINE_S="60")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tools", "serve.py"),
         "--generate", "--zoo-gpt", "tiny", "--platform", "cpu",
         "--host", "127.0.0.1", "--port", "0", "--max-slots", "2",
         "--kv-buckets", "160", "--no-warmup"],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    port = None
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if "serving on http://" in line:
                port = int(line.split("http://")[1].split()[0]
                           .rsplit(":", 1)[1])
                break
        assert port, "server never reported its address"
        base = f"http://127.0.0.1:{port}"

        results = {}

        def client(ci):
            body = json.dumps({"tokens": [3 + ci, 7, 11],
                               "max_new_tokens": 120}).encode()
            req = urllib.request.Request(f"{base}/v1/generate",
                                         data=body)
            with urllib.request.urlopen(req, timeout=120) as r:
                toks, done = 0, None
                for ln in r:
                    obj = json.loads(ln)
                    if "token" in obj:
                        toks += 1
                    if obj.get("done"):
                        done = obj
                results[ci] = (toks, done)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        # wait until generation is demonstrably resident (tokens flow)
        _wait(lambda: _gen_active(base), timeout=90,
              what="resident generation load")
        proc.send_signal(signal.SIGTERM)
        time.sleep(0.1)
        # during the drain window: admission sheds 429 + structured
        # payload, readiness 503 ("draining"), liveness 200
        try:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/v1/generate",
                data=json.dumps({"tokens": [1, 2],
                                 "max_new_tokens": 4}).encode()),
                timeout=10)
            raise AssertionError("draining admission should be 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert json.loads(e.read())["reason"] == "draining"
        try:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
            raise AssertionError("draining readiness should be 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
        with urllib.request.urlopen(f"{base}/livez", timeout=10) as r:
            assert json.loads(r.read())["status"] == "alive"
        for t in threads:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in threads)
        # every accepted stream finished completely: full budget + a
        # clean done trailer (never a reset mid-stream)
        assert sorted(results) == [0, 1, 2, 3]
        for toks, done in results.values():
            assert done is not None and done.get("done")
            assert toks == 120
        assert proc.wait(timeout=90) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def _gen_active(base):
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
        h = json.loads(r.read())
    return h.get("generation", {}).get("slots", {}).get("active", 0) > 0
