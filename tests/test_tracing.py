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


# ---------------------------------------------------------------------------
# the device's work, named: component scopes and the program table
# ---------------------------------------------------------------------------

# optimized HLO as the TPU compiler writes it, cut to what the rules
# read: a weight-gradient matmul with adamw fused behind it (rooted in
# ``optim``), an FFN backward matmul, a scan whose body is scoped, an
# unscoped copy, a multi-output fusion whose root is a bare tuple
_HLO = '''HloModule jit_step, is_scheduled=true

%fused_dw1 (p0: bf16[8,16], p1: bf16[8,32]) -> f32[16,32] {
  %p0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[8,32]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.7 = f32[16,32]{1,0:T(8,128)} convolution(%p0, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(step)/transpose(jvp(ffn))/up/dot_general" stack_frame_id=4}
  %c = f32[]{:T(256)} constant(0.9)
  %b = f32[16,32]{1,0:T(8,128)} broadcast(%c), dimensions={}, metadata={op_name="jit(step)/optim/mul"}
  ROOT %mul.3 = f32[16,32]{1,0:T(8,128)} multiply(%convolution.7, %b), metadata={op_name="jit(step)/optim/mul" stack_frame_id=9}
}

%fused_down (p0.1: bf16[8,32], p1.1: bf16[32,16]) -> bf16[8,16] {
  %p0.1 = bf16[8,32]{1,0} parameter(0)
  %p1.1 = bf16[32,16]{1,0} parameter(1)
  ROOT %dot.2 = bf16[8,16]{1,0} dot(%p0.1, %p1.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(ffn))/down/dot_general"}
}

%fused_stats (p0.2: bf16[8,16]) -> (bf16[8,16], f32[8]) {
  %p0.2 = bf16[8,16]{1,0} parameter(0)
  %add.1 = bf16[8,16]{1,0} add(%p0.2, %p0.2), metadata={op_name="jit(step)/jvp(attn/out)/add"}
  %r = f32[8]{0} reduce(%add.1), dimensions={1}, metadata={op_name="jit(step)/jvp(attn/out)/reduce_sum"}
  ROOT %tuple.9 = (bf16[8,16]{1,0}, f32[8]{0}) tuple(%add.1, %r)
}

%body (arg: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %arg = (s32[], bf16[8,16]{1,0}) parameter(0)
  %x = bf16[8,16]{1,0} get-tuple-element(%arg), index=1
  %tanh.4 = bf16[8,16]{1,0} tanh(%x), metadata={op_name="jit(step)/while/body/closed_call/ssm/tanh"}
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], bf16[8,16]{1,0}) tuple(%i, %tanh.4)
}

%cond (arg.1: (s32[], bf16[8,16])) -> pred[] {
  %arg.1 = (s32[], bf16[8,16]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %n = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%i.1, %n), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main (a: bf16[8,16], g: bf16[8,32], w: bf16[32,16]) -> f32[16,32] {
  %a = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="a"}
  %g = bf16[8,32]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="g"}
  %w = bf16[32,16]{1,0} parameter(2), metadata={op_name="w"}
  %copy.5 = bf16[8,16]{1,0} copy(%a), metadata={op_name="jit(step)/reshape"}
  %copy-start = (bf16[32,16]{1,0:S(1)}, bf16[32,16]{1,0}, u32[]{:S(2)}) copy-start(%w), cross_program_prefetch_index=0
  %copy-done = bf16[32,16]{1,0:S(1)} copy-done(%copy-start)
  %copy.6 = bf16[8,16]{1,0} copy(%a)
  %fusion.2 = bf16[8,16]{1,0} fusion(%g, %copy-done), kind=kOutput, calls=%fused_down, metadata={op_name="jit(step)/transpose(jvp(ffn))/down/dot_general"}
  %fusion.9 = (bf16[8,16]{1,0}, f32[8]{0}) fusion(%fusion.2), kind=kLoop, calls=%fused_stats
  %gte = bf16[8,16]{1,0} get-tuple-element(%fusion.9), index=0
  %zero = s32[] constant(0)
  %init = (s32[], bf16[8,16]{1,0}) tuple(%zero, %gte)
  %while.5 = (s32[], bf16[8,16]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  %ragged_attention = bf16[8,16]{1,0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/core/pallas_call"}
  ROOT %fusion.1 = f32[16,32]{1,0:T(8,128)} fusion(%a, %g), kind=kOutput, calls=%fused_dw1, metadata={op_name="jit(step)/optim/mul" stack_frame_id=9}
}
'''


@pytest.mark.parametrize("name,want", [
    # a dot inside a fusion rooted in optim takes the dot's path
    ("fusion.1", ("ffn", "up", "bwd")),
    ("fusion.2", ("ffn", "down", "bwd")),
    # a root without metadata: the fusion's one component
    ("fusion.9", ("attn", "out", "fwd")),
    # the loop's body is scoped; the loop is no leaf
    ("tanh.4", ("ssm", "", "fwd")),
    ("while.5", None),
    ("copy.5", ("unscoped", "", "fwd")),
    # the compiler's own instructions (no metadata at all): the scope of
    # what waits for them; nothing waits for copy.6
    ("copy-start", ("ffn", "down", "bwd")),
    ("copy-done", ("ffn", "down", "bwd")),
    ("copy.6", ("unscoped", "", "fwd")),
    ("ragged_attention", ("attn", "core", "fwd")),
    # the loop's bookkeeping carries no word of the vocabulary
    ("lt.1", ("unscoped", "", "fwd")),
    ("a", None), ("gte", None),
])
def test_hlo_scopes_rules_on_hand_written_text(name, want):
    scopes, mixed = tracing.hlo_scopes(_HLO)
    assert scopes.get(name) == want
    assert mixed == {"fusion.1"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jit(main)/transpose(jvp(ffn))/down/dot_general",
     ("ffn", "down", "bwd")),
    ("jit(_step)/jvp(attn/core)/div", ("attn", "core", "fwd")),
    ("jit(_step)/ffn/up/jit(_where)/select_n", ("ffn", "up", "fwd")),
    # the first vocabulary word is the component: a norm inside ffn
    ("jit(_step)/ffn/up/norm/rsqrt", ("ffn", "up", "fwd")),
    ("jit(_step)/norm/rsqrt", ("norm", "", "fwd")),
    # a part that is not one of the component's is no part
    ("jit(_step)/optim/up/mul", ("optim", "", "fwd")),
    # a jitted helper called like a component is not one
    ("jit(_step)/jit(norm)/mul", ("unscoped", "", "fwd")),
    ("jit(scanned)/while/body/closed_call/cache/write/dynamic_update_slice",
     ("cache", "write", "fwd")),
    ("", ("unscoped", "", "fwd")),
])
def test_scope_of_a_path(op_name, want):
    assert tracing._scope_of(op_name) == want


def test_every_named_scope_in_the_package_is_in_the_vocabulary():
    """The literal of every jax.named_scope under mxnet_tpu/ is a path of
    tracing.COMPONENTS, and every site ISSUE 37 lists has some."""
    import re
    root = os.path.dirname(os.path.abspath(mx.__file__))
    found = {}
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    hits = re.findall(r'named_scope\(\s*"([^"]+)"',
                                      fh.read())
                if hits:
                    found[os.path.relpath(path, root)] = hits
    for path, hits in found.items():
        assert set(hits) <= set(tracing.COMPONENTS), (path, hits)
    for site in ("ops/transformer.py", "gluon/model_zoo/bert.py",
                 "gluon/model_zoo/gpt.py", "gluon/model_zoo/generation.py",
                 "gluon/model_zoo/phi4flash.py",
                 "gluon/model_zoo/cohere2moe.py", "parallel/moe.py",
                 "gluon/model_zoo/ouro.py", "serving/model.py",
                 "serving/hybrid.py", "serving/moe.py", "serving/loop.py",
                 "serving/kv_cache.py", "serving/gpt.py",
                 "parallel/spmd.py"):
        assert site in found, site
    # first segments and parts hang together
    for path in tracing.COMPONENTS:
        assert path.split("/")[0] in tracing.COMPONENTS


def _toy_programs():
    import jax
    import jax.numpy as jnp

    def _step(w, x):
        with jax.named_scope("ffn/up"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("optim"):
            return h, w * 0.5

    def _prefill(x):
        with jax.named_scope("embed"):
            return x + 1.0

    return _step, _prefill


def test_program_table_after_two_registered_builds_and_one_unregistered():
    import jax
    import jax.numpy as jnp
    tracing.reset()
    _step, _prefill = _toy_programs()
    step = tracing.program(_step, "decode", "toy", attrs={"slots": 8},
                           donate_argnums=(1,))
    prefill = tracing.program(_prefill, "prefill", "toy")
    heard = []
    listen = lambda e, d, **kw: heard.append(d) if e.rsplit(  # noqa: E731
        "/", 1)[-1] in ("jaxpr_trace_duration",
                        "jaxpr_to_mlir_module_duration",
                        "backend_compile_duration") else None
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with tracing.span("model.step.dispatch") as outer:
            step(jnp.ones((16, 16)), jnp.ones((4, 16)))
        prefill(jnp.ones((4,)))
        jax.jit(lambda a: a * 3.0)(jnp.ones((5,)))      # nobody's
        table = {(p.module, p.role): p for p in tracing.programs()}
        total = sum(p.seconds() for p in tracing.programs())
    finally:
        from jax._src import monitoring as _mon
        _mon.unregister_event_duration_listener(listen)
    dec = table[("jit__step", "decode")]
    assert (dec.family, dec.attrs) == ("toy", {"slots": 8})
    assert [set(e) >= {"args", "trace_s", "lower_s"} and
            ("compile_s" in e) != ("load_s" in e) for e in dec.built()] \
        == [True]
    assert all(v > 0 for k, v in dec.built()[0].items()
               if k.endswith("_s"))
    assert "float32[16,16], float32[4,16]" == dec.built()[0]["args"]
    assert table[("jit__prefill", "prefill")].seconds("trace") > 0
    anon = table[("jit__lambda_", None)]
    assert anon.shapes[0]["builds"] == 1 and anon.seconds() > 0
    # the table accounts for every second jax reported
    assert total == pytest.approx(sum(heard), rel=1e-9)
    # the second call of a shape builds nothing; a new shape adds a line
    before = dec.describe()
    step(jnp.ones((16, 16)), jnp.ones((4, 16)))
    assert dec.describe() == before
    step(jnp.ones((16, 16)), jnp.ones((8, 16)))
    assert len(dec.built()) == 2
    # each stage is a retroactive span under the span open at the call
    stages = [r for r in tracing.spans()
              if r["name"].startswith("program.")
              and r["attrs"]["program"] == "jit__step"]
    assert [r["name"] for r in stages[:3]] == [
        "program.trace", "program.lower", "program.compile"] or \
        [r["name"] for r in stages[:3]] == [
        "program.trace", "program.lower", "program.load"]
    assert all(r["parent_id"] == outer.span_id and
               r["attrs"]["role"] == "decode" for r in stages[:3])
    # the export carries the table
    exported = tracing.export_trace_events()["programs"]
    assert json.loads(json.dumps(exported))[0]["module"] == "jit__step"
    assert metrics.hist_stats("mxnet_program_build_seconds",
                              stage="trace")[1] > 0


def test_scopes_on_demand_reads_the_compiled_program_and_adds_no_build():
    import jax.numpy as jnp
    tracing.reset()
    _step, _ = _toy_programs()
    step = tracing.program(_step, "decode", "toy")
    step(jnp.ones((16, 16)), jnp.ones((4, 16)))
    rec = next(p for p in tracing.programs() if p.role == "decode")
    seconds = rec.seconds()
    scopes = rec.scopes()
    assert ("ffn", "up", "fwd") in scopes.values()
    assert ("optim", "", "fwd") in scopes.values()
    # what the reading itself built is marked and in no sum
    assert rec.seconds() == seconds and len(rec.built()) == 1
    assert all(e.get("reading") for e in rec.shapes[1:])
    # an unregistered function has no callable to read
    assert tracing.Program("jit_f").scopes() is None


def test_a_second_instance_keeps_the_seconds_and_only_its_own_callable():
    import jax.numpy as jnp
    tracing.reset()
    _step, _ = _toy_programs()
    first = tracing.program(_step, "decode", "toy")
    first(jnp.ones((16, 16)), jnp.ones((4, 16)))
    _step2, _ = _toy_programs()
    second = tracing.program(_step2, "decode", "toy")
    rec = [p for p in tracing.programs() if p.role == "decode"]
    assert len(rec) == 1 and rec[0].jitted is second
    assert len(rec[0].built()) == 1 and rec[0].seconds() > 0
    assert rec[0].scopes(0) is None       # the older instance's shape
    with pytest.raises(ValueError, match="role"):
        tracing.program(_step, "decoding")
    tracing.reset()
    assert tracing.programs() == []


def test_an_engine_traces_each_program_once_a_shape_and_reads_nothing():
    """Constructing an engine, warming it and running steps traces each
    program once a shape; nothing on that path reads the HLO."""
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import DecodeModel, GenerationEngine
    tracing.reset()
    mx.random.seed(0)
    net = GPTModel(vocab_size=97, num_layers=1, units=32, hidden_size=48,
                   num_heads=4, max_length=64, dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(net), max_slots=2,
                           kv_buckets=(16, 32), max_tokens=8)
    eng.warmup()
    warmed = {p.module: len(p.built()) for p in tracing.programs()
              if p.role}
    stream = eng.submit(onp.array([5, 9, 3], "int32"), max_new_tokens=4)
    while not stream.finished:
        eng.run_iteration()
    roles = {p.role: p for p in tracing.programs() if p.role}
    assert {"decode", "prefill", "select", "cache_write"} <= set(roles)
    assert all(p.family == "gpt" for p in roles.values()
               if p.role in ("decode", "prefill", "select"))
    for p in tracing.programs():
        if p.role is None:
            continue
        # nothing was built after the warm-up, nothing twice, nothing
        # by a reading of the HLO
        assert len(p.built()) == warmed[p.module], p.module
        args = [(e["args"], str(e.get("attrs"))) for e in p.shapes]
        assert len(set(args)) == len(args), (p.module, args)
        assert not any(e.get("reading") for e in p.shapes)
    assert roles["decode"].seconds("trace", "lower") > 0
    assert roles["decode"].scopes() is not None


# ---------------------------------------------------------------------------
# profiler.device_summary: each op under the program that ran it
# ---------------------------------------------------------------------------

class _Text:
    """A record of the table that answers ``hlo_text`` from a string."""

    def __init__(self, module, role, text):
        self.module, self.role, self._text = module, role, text

    def built(self):
        return [{}]

    def hlo_text(self, shape=-1):
        return self._text


def _module(name, instructions):
    lines = "\n".join(
        f'  {"ROOT " if i == len(instructions) - 1 else ""}%{n} = '
        f'f32[8]{{0}} {op}(%p), metadata={{op_name="jit(f)/{path}/x"}}'
        for i, (n, op, path) in enumerate(instructions))
    return (f"HloModule {name}\n\nENTRY %main (p: f32[8]) -> f32[8] {{\n"
            f"  %p = f32[8]{{0}} parameter(0)\n{lines}\n}}\n")


def test_device_summary_splits_two_programs_that_share_an_instruction_name():
    """jit__step and jit__prefill both have a fusion.1; the by-name
    reduction adds them up under one key, the sound one looks each
    event up in the program whose module event encloses it."""
    from mxnet_tpu import profiler
    programs = [
        _Text("jit__step", "decode", _module("jit__step", [
            ("fusion.1", "add", "ffn/up"), ("copy.2", "copy", "other")])),
        _Text("jit__prefill", "prefill", _module("jit__prefill", [
            ("fusion.1", "add", "attn/core")])),
    ]
    ms = 1_000_000
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__step(11)", 0, 10 * ms),
                        ("jit__prefill(22)", 20 * ms, 30 * ms),
                        ("jit__step(11)", 60 * ms, 10 * ms)],
        "XLA Ops": [
            ("%fusion.1 = f32[8]{0} add(f32[8]{0} %p)", 0, 6 * ms),
            ("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 6 * ms, 2 * ms),
            ("%fusion.1 = f32[8]{0} add(f32[8]{0} %p)", 20 * ms, 25 * ms),
            ("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
             60 * ms, 9 * ms),
            ("%fusion.1 = f32[8]{0} add(f32[8]{0} %p)", 60 * ms, 6 * ms),
        ]}}
    got = profiler.summarize_planes(planes, programs)
    assert got["by_component"] == pytest.approx({
        "ffn/up fwd": 0.012, "attn/core fwd": 0.025,
        "unscoped fwd": 0.002})
    assert got["by_role"] == pytest.approx({"decode": 0.014,
                                            "prefill": 0.025})
    assert got["by_program"]["jit__step"]["runs"] == 2
    assert got["by_program"]["jit__prefill"]["seconds"] == \
        pytest.approx(0.025)
    # the loop is around its children, not beside them
    assert got["ops_s"] == pytest.approx(0.039)
    # what keying by the name alone gives: one line for both programs
    by_name = {}
    for text, _, dur in planes["/device:TPU:0"]["XLA Ops"]:
        name = text.split(" = ")[0].lstrip("%")
        by_name[name] = by_name.get(name, 0.0) + dur / 1e9
    assert by_name["fusion.1"] == pytest.approx(0.037)
    # a window cuts the events
    cut = profiler.summarize_planes(planes, programs,
                                    window=(0, 10 * ms))
    assert cut["ops_s"] == pytest.approx(0.008)
    assert cut["by_program"]["jit__step"]["runs"] == 1
