"""Distributed-tracing span runtime (mxnet_tpu/tracing.py): contextvar
parentage across threads and the serving batcher queue, W3C traceparent
propagation over the HTTP front end and the parameter-server frame
wire, tail-based retention under low head sampling, the bounded ring
buffer, the watchdog's active-span-tree dump, and the hard-off mode.

Beyond-reference observability behavior specified by ISSUE 16 (the
reference's profiler only covered single-process op windows).
"""
import http.client
import json
import os
import threading
import time
from concurrent.futures import Future

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import health, metrics, serving, tracing
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import (BucketPolicy, DynamicBatcher, ModelServer,
                               Request)

from tests.test_distributed import _free_port


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.configure(sample=1.0)
    yield
    tracing.configure()          # back to env-derived config, empty ring


def _names(recs):
    return {r["name"] for r in recs}


# ---------------------------------------------------------------------------
# propagation: threads + the batcher queue
# ---------------------------------------------------------------------------

def test_parentage_across_threads_and_batcher_queue():
    """capture()/attach() carries the trace onto a worker thread, and a
    Request submitted to the DynamicBatcher under a trace gets its
    queue.wait span parented under the submitting span."""
    done = threading.Event()
    with tracing.span("root", kind="unit") as root:
        ctx = tracing.capture()

        def work():
            with tracing.attach(ctx), tracing.child_span("worker.task"):
                pass
            done.set()

        threading.Thread(target=work, daemon=True).start()
        assert done.wait(10)

        p = BucketPolicy(batch_buckets=(1,))
        b = DynamicBatcher(p, timeout_ms=1, queue_limit=4)
        sample = (onp.ones(3, "float32"),)
        b.submit(Request(sample, p.bucket_key(sample), Future(), None))
        take = b.next_batch()
        assert take is not None and len(take) == 1
        b.close()

    recs = tracing.spans(root.trace_id)
    by = {r["name"]: r for r in recs}
    assert {"root", "worker.task", "queue.wait"} <= set(by)
    # both hops parent under the span that was active at hand-off time
    assert by["worker.task"]["parent_id"] == root.span_id
    assert by["worker.task"]["thread"] != by["root"]["thread"]
    assert by["queue.wait"]["parent_id"] == root.span_id
    # nothing leaked into a second trace
    assert len({r["trace_id"] for r in recs}) == 1


# ---------------------------------------------------------------------------
# propagation: the HTTP wire
# ---------------------------------------------------------------------------

def test_traceparent_http_round_trip_on_the_wire():
    """A client-sent traceparent header continues the client's trace:
    the server's spans carry the client's trace id (http.request is a
    remote child of the client's span id), the response echoes the
    header, and GET /v1/traces exports them on the raw wire."""
    tid, sid = "a" * 32, "b" * 16
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((2, 12), dtype="float32"))
    model = serving.load_served(net)
    srv = ModelServer(model, model.default_policy(max_batch=2),
                      timeout_ms=3, warmup=True).start()
    httpd = serving.make_http_server(srv, port=0)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        host, port = httpd.server_address[:2]
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/v1/inference",
                     json.dumps({"data": [0.5] * 12}),
                     {"Content-Type": "application/json",
                      "traceparent": f"00-{tid}-{sid}-01"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200 and "predictions" in body
        echo = resp.getheader("traceparent")
        assert echo is not None and echo.split("-")[1] == tid

        conn.request("GET", "/v1/traces", headers={})
        payload = json.loads(conn.getresponse().read())
        conn.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()

    mine = [e for e in payload["traceEvents"]
            if e.get("ph") == "X" and e["args"].get("trace_id") == tid]
    by = {e["name"]: e for e in mine}
    assert {"http.request", "queue.wait"} <= set(by), sorted(by)
    assert by["http.request"]["args"]["parent_id"] == sid


# ---------------------------------------------------------------------------
# propagation: the PS frame wire
# ---------------------------------------------------------------------------

def test_ps_frame_carries_trace_across_push(monkeypatch):
    """A worker push under a trace stamps its traceparent into the PS
    frame header; the server's handling shows up as a ps.handle remote
    child span with the worker's trace id."""
    from mxnet_tpu.kvstore_async import KVStoreDistAsync, run_server

    port = _free_port()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    ev = threading.Event()
    th = threading.Thread(target=run_server, args=(port, 1, ev),
                          daemon=True)
    th.start()
    assert ev.wait(20), "parameter server did not come up"
    kv = KVStoreDistAsync()
    try:
        kv.init("w", mx.np.zeros(4))        # untraced: no header field
        with tracing.span("push.root") as root:
            kv.push("w", mx.np.array(onp.ones(4, "float32")))
            got = kv.pull("w", out=mx.np.zeros(4)).asnumpy()
        assert onp.allclose(got, 1.0)
    finally:
        kv.stop_servers()
        th.join(10)

    recs = tracing.spans(root.trace_id)
    ps = [r for r in recs if r["name"] == "ps.handle"]
    assert ps, f"no ps.handle span in the push trace: {_names(recs)}"
    # a REMOTE child: same trace id, parented on the worker-side span
    # that was on the wire, handled on the server thread
    assert all(r["parent_id"] == root.span_id for r in ps)
    assert any(r["attrs"].get("cmd") == "P" for r in ps)
    assert all(r["thread"] != root._thread for r in ps)


# ---------------------------------------------------------------------------
# tail-based retention
# ---------------------------------------------------------------------------

def test_tail_upgrade_keeps_slow_and_error_traces_at_low_sampling():
    """At 1% head sampling, a trace that lost the coin flip is still
    retained whole when one of its spans runs past MXNET_TRACE_SLOW_MS
    or exits with an exception."""
    tracing.configure(sample=0.01, slow_ms=20.0)

    def unsampled_root(body):
        # P(sampled) = 0.01 per attempt: 200 attempts make a sampled-
        # only streak vanishingly unlikely (1e-400)
        for _ in range(200):
            with tracing.span("tail.root") as root:
                sampled = tracing.current_context().sampled
                if not sampled:
                    body()
            if not sampled:
                return root
        pytest.fail("never drew an unsampled trace at sample=0.01")

    slow = unsampled_root(lambda: tracing.record_span(
        "tail.slow", time.perf_counter() - 0.05, time.perf_counter()))
    recs = tracing.spans(slow.trace_id)
    assert {"tail.root", "tail.slow"} <= _names(recs)

    def raise_in_child():
        with pytest.raises(ValueError):
            with tracing.child_span("tail.err"):
                raise ValueError("boom")

    err = unsampled_root(raise_in_child)
    recs = tracing.spans(err.trace_id)
    by = {r["name"]: r for r in recs}
    assert {"tail.root", "tail.err"} <= set(by)
    assert by["tail.err"]["status"] == "error"
    assert "boom" in by["tail.err"]["error"]

    # a fast, clean, unsampled trace is NOT retained
    fast = unsampled_root(lambda: None)
    assert tracing.spans(fast.trace_id) == []


# ---------------------------------------------------------------------------
# ring buffer bound
# ---------------------------------------------------------------------------

def test_ring_buffer_keeps_only_the_newest_spans():
    tracing.configure(sample=1.0, buffer_spans=8)
    for i in range(50):
        with tracing.span("ring", i=i):
            pass
    recs = tracing.spans()
    assert len(recs) == 8
    assert [r["attrs"]["i"] for r in recs] == list(range(42, 50))


# ---------------------------------------------------------------------------
# watchdog integration
# ---------------------------------------------------------------------------

def test_watchdog_dump_names_the_open_span_tree(tmp_path, monkeypatch):
    """A hang-watchdog diagnostic dump includes the currently-open
    spans as an indented tree, so a stall names the span it wedged in."""
    metrics.reset()
    monkeypatch.setenv("MXNET_HEALTH_DIAG_DIR", str(tmp_path))
    with tracing.span("stall.root", step=7):
        with tracing.child_span("stall.child"):
            with health.watch_section("unit.trace", deadline_s=0.05):
                time.sleep(0.3)
    deadline = time.monotonic() + 10
    while (metrics.value("mxnet_health_events_total", kind="hang") < 1
           and time.monotonic() < deadline):
        time.sleep(0.02)
    path = health.last_dump_path()
    assert path and os.path.dirname(path) == str(tmp_path)
    text = open(path).read()
    assert "== active spans ==" in text
    assert "stall.root trace=" in text and "step=7" in text
    # the child is nested (indented) under the root
    assert "\n  stall.child trace=" in text


# ---------------------------------------------------------------------------
# hard off
# ---------------------------------------------------------------------------

def test_sample_zero_records_nothing_ever():
    """MXNET_TRACE_SAMPLE=0 is fully off: slow spans, error spans and
    explicit record_span calls all record nothing, and no trace context
    exists to propagate."""
    tracing.configure(sample=0.0, slow_ms=0.0)
    with tracing.span("off.slow"):
        assert tracing.current_context() is None
        assert tracing.traceparent() is None
        time.sleep(0.01)
    with pytest.raises(ValueError):
        with tracing.span("off.err"):
            raise ValueError("boom")
    tracing.record_span("off.rec", 0.0, 1.0)
    assert tracing.parse_traceparent(f"00-{'a'*32}-{'b'*16}-01") is None
    assert tracing.spans() == []


# ---------------------------------------------------------------------------
# the device trace's timeline, and traces for work submitted under none
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """{event name: [(start_ns, duration_ns)]} of the newest profile's
    /host:CPU plane."""
    import glob
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"the profiler wrote no .xplane.pb under {trace_dir}"
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    events = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.duration_ns)))
    return events


def test_spans_show_on_the_host_plane_of_a_jax_profile(tmp_path):
    """Any jax.profiler session shows the program's spans on /host:CPU:
    engine.iteration and its children, by name, as long as the
    records say."""
    import jax
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import DecodeModel, GenerationEngine
    mx.random.seed(0)
    net = GPTModel(vocab_size=97, num_layers=1, units=32, hidden_size=48,
                   num_heads=4, max_length=64, dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(net), max_slots=2,
                           kv_buckets=(16,), max_tokens=8)
    eng.warmup()
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        stream = eng.submit(onp.array([5, 9, 3], "int32"), max_new_tokens=3)
        while not stream.finished:
            eng.run_iteration()
    events = _host_events(str(tmp_path))
    if not events:
        pytest.skip("the CPU profiler wrote no /host:CPU plane")
    recs = tracing.spans()
    for name in ("engine.iteration", "engine.prefill", "model.prefill",
                 "kv.write_prompt", "model.select",
                 "model.step.dispatch", "model.step.readback",
                 "engine.emit"):
        n = sum(r["name"] == name for r in recs)
        assert n and len(events.get(name, ())) == n, (name, n)
    assert "queue.wait" not in events        # retroactive: ring only
    # the annotation holds the record's interval
    durs = sorted(d for _, d in events["engine.iteration"])
    want = sorted(1e9 * (r["t_end"] - r["t_begin"]) for r in recs
                  if r["name"] == "engine.iteration")
    for got, rec in zip(durs, want):
        assert rec <= got + 1e3 and got - rec < 1e6, (got, rec)


def test_root_context_gives_untraced_work_a_trace():
    """root_context() is a fresh head-sampled trace with no span of its
    own: spans recorded under attach() land in it; off when tracing is
    off."""
    assert tracing.current_context() is None
    ctx = tracing.root_context()
    assert tracing.current_context() is None      # not activated
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    with tracing.attach(ctx), tracing.child_span("late.work"):
        pass
    tracing.record_span("late.wait", 1.0, 2.0, ctx=ctx)
    recs = tracing.spans(ctx.trace_id)
    assert _names(recs) == {"late.work", "late.wait"}
    assert all(r["parent_id"] == ctx.span_id for r in recs)
    assert tracing.root_context().trace_id != ctx.trace_id
    tracing.configure(sample=0.0)
    assert tracing.root_context() is None


def test_ring_default_holds_the_benchmarks_readers_window():
    """The readers of chipbench run up to 90 s after the spans they read
    were recorded; the default ring must hold that at the busiest
    measured rate (PERF.md, Findings PR 26)."""
    tracing.configure()
    assert tracing._RT.cap == tracing._BUFFER_SPANS
    assert tracing._BUFFER_SPANS & (tracing._BUFFER_SPANS - 1) == 0
    assert tracing._BUFFER_SPANS >= 90 * 160
