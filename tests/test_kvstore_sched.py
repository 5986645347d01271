"""Overlapped gradient-reduction scheduler (ISSUE 14).

Covers: bucket composition (registration order, byte budget, priority
independence), priority-ordered dispatch, trainer parity overlapped vs
serialized, the wired ``priority`` parameter on the sync store, 2-bit
error-feedback residual determinism across bucket recomposition,
compressed-vs-none convergence parity on the lstm micro config, the
``kvstore.bucket`` watchdog site, comm-thread error propagation, and
the dist_async scheduled path (seq-at-enqueue exactly-once).
"""
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore_sched as ks
from mxnet_tpu import metrics


def _arr(n, fill=1.0):
    return mx.np.array(onp.full((n,), fill, dtype="float32"))


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_bucket_plan_registration_order_and_budget():
    keys = list(range(6))
    vals = [_arr(100), _arr(100), _arr(300), _arr(50), _arr(50),
            _arr(400)]
    prios = [0, -1, -2, -3, -4, -5]
    # budget of 800 bytes = 200 f32 elements
    buckets = ks.plan_buckets(keys, vals, prios, bucket_bytes=800)
    assert [b.keys for b in buckets] == [[0, 1], [2], [3, 4], [5]]
    # composition is registration-contiguous and byte-bounded; a value
    # at/above the budget gets its own bucket
    assert [b.priority for b in buckets] == [0, -2, -3, -5]
    # priorities order dispatch, never membership: scrambling them
    # leaves composition identical
    scrambled = ks.plan_buckets(keys, vals, [5, 0, -9, 3, 1, 2],
                                bucket_bytes=800)
    assert [b.keys for b in scrambled] == [b.keys for b in buckets]


def test_priority_orders_strict_dispatch():
    """strict_order rounds execute purely by descending priority (the
    SPMD collective-sequence contract)."""
    ran = []
    done = threading.Event()

    def reduce_fn(bucket):
        ran.append(bucket.keys[0])
        if len(ran) == 4:
            done.set()

    # one entry per bucket (budget 4 bytes), priorities favor key 3
    rnd = ks.submit([0, 1, 2, 3], [_arr(1)] * 4, [-3, -1, -2, 0],
                    reduce_fn, bucket_bytes=4, strict_order=True)
    assert done.wait(10)
    rnd.finish()
    assert ran == [3, 1, 2, 0]


def test_comm_thread_error_propagates_and_cancels():
    def reduce_fn(bucket):
        raise RuntimeError(f"boom {bucket.keys[0]}")

    rnd = ks.submit([0, 1], [_arr(1), _arr(1)], [0, -1], reduce_fn,
                    bucket_bytes=4, strict_order=True)
    with pytest.raises(RuntimeError, match="boom 0"):
        for b in rnd.buckets:
            rnd.wait(b)
    # the second bucket's error was never consumed by a wait — finish
    # drains the round and re-raises it (errors are never swallowed)
    with pytest.raises(RuntimeError, match="boom 1"):
        rnd.finish()
    rnd.finish()     # idempotent after the drain


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def _train(overlap, optimizer="adam", opt_args=None, steps=5,
           compression=None, bucket_bytes=1024):
    os.environ["MXNET_KV_OVERLAP"] = overlap
    os.environ["MXNET_KV_BUCKET_BYTES"] = str(bucket_bytes)
    # a (negligibly fast) synthetic wire: the scheduler only engages
    # when the store has an actual wire to hide — a plain
    # single-process 'device' store would take the serialized path
    os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "10000"
    try:
        mx.random.seed(0)
        net = mx.gluon.nn.Sequential()
        net.add(mx.gluon.nn.Dense(32, activation="relu"),
                mx.gluon.nn.Dense(8))
        net.initialize()
        net(mx.np.zeros((2, 16)))
        tr = mx.gluon.Trainer(net.collect_params(), optimizer,
                              opt_args or {"learning_rate": 1e-2},
                              compression_params=compression)
        loss_fn = mx.gluon.loss.L2Loss()
        rng = onp.random.RandomState(0)
        losses = []
        for _ in range(steps):
            x = mx.np.array(rng.uniform(-1, 1, (4, 16)).astype("f4"))
            y = mx.np.array(rng.uniform(-1, 1, (4, 8)).astype("f4"))
            with mx.autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(4)
            losses.append(loss.asnumpy().tobytes())
        params = [p.data().asnumpy().copy()
                  for p in net.collect_params().values()]
        return losses, params
    finally:
        os.environ.pop("MXNET_KV_OVERLAP", None)
        os.environ.pop("MXNET_KV_BUCKET_BYTES", None)
        os.environ.pop("MXNET_KV_SYNTH_WIRE_GBPS", None)


@pytest.mark.parametrize("optimizer,opt_args", [
    ("adam", {"learning_rate": 1e-2}),
    ("sgd", {"learning_rate": 1e-2, "momentum": 0.9}),
])
def test_overlapped_trainer_bit_parity(optimizer, opt_args):
    """Only the schedule moves — weights and losses stay bit-identical
    between the overlapped and serialized reduction paths."""
    l1, p1 = _train("1", optimizer, opt_args)
    l0, p0 = _train("0", optimizer, opt_args)
    assert l1 == l0
    for a, b in zip(p1, p0):
        assert (a == b).all()


def test_overlapped_trainer_2bit_replay_identical():
    """Per-key error-feedback residuals are deterministic under the
    scheduler: two overlapped compressed runs replay identically."""
    comp = {"type": "2bit", "threshold": 1e-3}
    la, _ = _train("1", compression=comp)
    lb, _ = _train("1", compression=comp)
    assert la == lb


def test_trainer_passes_forward_order_priorities():
    """The trainer wires priority=-param_index into the round — the
    reference trainer.py convention, so first-needed params lead."""
    os.environ["MXNET_KV_OVERLAP"] = "1"
    try:
        seen = {}
        orig = ks.submit

        def spy(keys, vals, priorities, *a, **kw):
            seen["prios"] = list(priorities)
            seen["keys"] = list(keys)
            return orig(keys, vals, priorities, *a, **kw)

        ks.submit = spy
        try:
            _train("1", steps=1)
        finally:
            ks.submit = orig
        assert seen["prios"] == [-k for k in seen["keys"]]
    finally:
        os.environ.pop("MXNET_KV_OVERLAP", None)


def test_public_allreduce_grads_returns_reduced(monkeypatch):
    """The documented allreduce_grads -> inspect/clip grads ->
    update() pattern: a DIRECT call must return with gradients fully
    reduced even under the overlapped scheduler (only step() defers
    the waits into the update)."""
    monkeypatch.setenv("MXNET_KV_OVERLAP", "1")
    monkeypatch.setenv("MXNET_KV_SYNTH_WIRE_GBPS", "10000")
    monkeypatch.setenv("MXNET_KV_BUCKET_BYTES", "1024")
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4, in_units=8)
    net.initialize()
    net(mx.np.zeros((1, 8)))
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1})
    with mx.autograd.record():
        loss = mx.gluon.loss.L2Loss()(
            net(mx.np.ones((2, 8))), mx.np.ones((2, 4)))
    loss.backward()
    tr.allreduce_grads()
    # no round may still be pending — grads are safe to read/modify
    assert getattr(tr, "_sched_round", None) is None
    for p in net.collect_params().values():
        assert p.data().grad is not None
    tr.update(2)         # caller-already-reduced path still works


# ---------------------------------------------------------------------------
# the wired priority parameter on the sync store
# ---------------------------------------------------------------------------

class _RecordingICI(mx.kvstore.KVStoreICI):
    """Single-process stand-in that forces the bucketed reduce path and
    records the flat-bucket dispatch order."""

    def __init__(self):
        super().__init__("ici")
        self.reduced = []

    @staticmethod
    def _needs_reduction(data):
        return True

    def _reduce_flat(self, flat):
        self.reduced.append(int(flat.shape[0]))
        return flat


def test_kvstore_push_priority_orders_buckets(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "100")
    kv = _RecordingICI()
    keys = ["a", "b", "c"]
    vals = [_arr(80, 1.0), _arr(120, 2.0), _arr(60, 3.0)]
    kv.init(keys, [v.copy() for v in vals])
    kv.reduced.clear()
    # buckets by size/order: [a(80)], [b(120) alone >= bound], [c(60)]
    # priority list: c wins, then a, then b
    kv.push(keys, vals, priority=[-1, -2, 0])
    assert kv.reduced == [60, 80, 120]
    # int priority (the common case) keeps registration order
    kv.reduced.clear()
    kv.push(keys, vals, priority=0)
    assert kv.reduced == [80, 120, 60]
    with pytest.raises(mx.MXNetError, match="priority list"):
        kv.push(keys, vals, priority=[0])


# ---------------------------------------------------------------------------
# 2bit error-feedback residuals across bucket recomposition
# ---------------------------------------------------------------------------

class _LoopbackICI(mx.kvstore.KVStoreICI):
    """ICI store whose gather is a single-process loopback, so the
    compressed wire path (_reduce_flat_compressed + per-key residuals)
    runs without a multi-process job."""

    def _gather_decode_sum(self, payloads, decode, cache_key):
        import jax.numpy as jnp
        return decode(*[p[None, :] for p in payloads])


def test_2bit_residual_survives_bucket_recomposition():
    """Error-feedback mass deferred for a key must re-offer on the next
    push of THAT key even when the bucket composition changes between
    pushes — the per-key ``segs`` residual layout."""
    import jax.numpy as jnp
    kv = _LoopbackICI()
    kv.set_gradient_compression({"type": "2bit", "threshold": 1.0})

    ga = onp.array([0.6, -0.6], dtype="f4")     # below threshold
    gb = onp.array([0.7, 0.7], dtype="f4")

    # push 1: one bucket holding both keys
    flat = jnp.asarray(onp.concatenate([ga, gb]))
    out1 = onp.asarray(kv._reduce_flat_compressed(
        flat, "2bit", [("a", 2), ("b", 2)]))
    assert (out1 == 0).all()                    # everything deferred

    # push 2: RECOMPOSED — each key now reduces in its own bucket.
    # residual(a)=ga, residual(b)=gb carried per key: 2nd offer crosses
    # the threshold exactly as an unbucketed per-key stream would.
    out2a = onp.asarray(kv._reduce_flat_compressed(
        jnp.asarray(ga), "2bit", [("a", 2)]))
    out2b = onp.asarray(kv._reduce_flat_compressed(
        jnp.asarray(gb), "2bit", [("b", 2)]))
    onp.testing.assert_allclose(out2a, [1.0, -1.0])
    onp.testing.assert_allclose(out2b, [1.0, 1.0])

    # and the residuals kept their per-key identity
    onp.testing.assert_allclose(
        onp.asarray(kv._ici_residuals["a"]), ga + ga - [1.0, -1.0],
        atol=1e-6)
    onp.testing.assert_allclose(
        onp.asarray(kv._ici_residuals["b"]), gb + gb - [1.0, 1.0],
        atol=1e-6)


def test_convergence_parity_2bit_vs_none_lstm_micro():
    """Compressed training tracks uncompressed on the lstm micro
    config: loss decreases and lands within a band of the lossless
    run."""
    vocab, embed, hidden, batch, seq = 120, 16, 16, 4, 6

    def build():
        mx.random.seed(7)

        class LM(mx.gluon.HybridBlock):
            def __init__(self):
                super().__init__()
                self.emb = mx.gluon.nn.Embedding(vocab, embed)
                self.rnn = mx.gluon.rnn.LSTM(hidden, num_layers=1,
                                             layout="NTC")
                self.out = mx.gluon.nn.Dense(vocab, flatten=False)

            def forward(self, x):
                return self.out(self.rnn(self.emb(x)))

        net = LM()
        net.initialize()
        net(mx.np.zeros((2, 3), dtype="int32"))
        return net

    def train(compression):
        net = build()
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.5},
                              compression_params=compression)
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
        rng = onp.random.RandomState(0)
        x = mx.np.array(rng.randint(0, vocab, (batch, seq))
                        .astype("int32"))
        y = mx.np.array(rng.randint(0, vocab, (batch, seq))
                        .astype("int32"))
        losses = []
        for _ in range(8):
            with mx.autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            tr.step(batch)
            losses.append(float(loss.asnumpy()))
        return losses

    base = train(None)
    comp = train({"type": "2bit", "threshold": 1e-3})
    assert base[-1] < base[0] and comp[-1] < comp[0], \
        (base[0], base[-1], comp[0], comp[-1])
    rel = abs(comp[-1] - base[-1]) / max(abs(base[-1]), 1e-9)
    assert rel < 0.25, f"2bit diverged from lossless: {rel:.3f} " \
                       f"({comp[-1]:.4f} vs {base[-1]:.4f})"


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_names_kvstore_bucket_site(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_HEALTH_STEP_DEADLINE_S", "0.05")
    monkeypatch.setenv("MXNET_HEALTH_DIAG_DIR", str(tmp_path))
    before = metrics.value("mxnet_health_watchdog_fires_total",
                           site="kvstore.bucket")

    def slow_reduce(bucket):
        time.sleep(0.25)

    rnd = ks.submit([0], [_arr(1)], [0], slow_reduce, bucket_bytes=4)
    rnd.wait(rnd.buckets[0])
    rnd.finish()
    after = metrics.value("mxnet_health_watchdog_fires_total",
                          site="kvstore.bucket")
    assert after > before


# ---------------------------------------------------------------------------
# dist_async: scheduled sends with enqueue-time seqs
# ---------------------------------------------------------------------------

def _start_server():
    import socket
    from mxnet_tpu import kvstore_async as ka
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ready = threading.Event()
    t = threading.Thread(target=ka.run_server, args=(port, 1, ready),
                         daemon=True)
    t.start()
    assert ready.wait(10)
    return port, t


def test_dist_async_scheduled_matches_local(monkeypatch):
    """The bucketed comm-thread path over a live PS produces the same
    trajectory as the single-process update-on-kvstore store, and its
    enqueue-time seqs keep pushes exactly-once."""
    port, t = _start_server()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    monkeypatch.setenv("MXNET_KV_OVERLAP", "1")
    monkeypatch.setenv("MXNET_KV_BUCKET_BYTES", "1024")

    def build():
        mx.random.seed(3)
        net = mx.gluon.nn.Dense(4, in_units=8)
        net.initialize()
        net(mx.np.zeros((1, 8)))
        return net

    def fit(net, kvstore, **kw):
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1}, kvstore=kvstore,
                              **kw)
        loss_fn = mx.gluon.loss.L2Loss()
        rng = onp.random.RandomState(1)
        for _ in range(4):
            x = mx.np.array(rng.uniform(-1, 1, (4, 8)).astype("f4"))
            y = mx.np.array(rng.uniform(-1, 1, (4, 4)).astype("f4"))
            with mx.autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(4)
        return tr

    net_a = build()
    tr = fit(net_a, "dist_async")
    kv = tr._kvstore
    # seq-at-enqueue: every scheduled bucket drew its seq before the
    # comm thread ran it; a replay of the last frame seq is deduped
    stats0 = kv.server_stats()[0]
    dup_before = metrics.value("mxnet_ps_deduped_pushes_total")
    keys = [0, 1]
    vals = [onp.zeros(p.data().shape, "f4")
            for p in net_a.collect_params().values()]
    seqs = {0: kv._seqs[0]}       # reuse the LAST consumed seq
    kv._push_impl(keys, [mx.np.array(v) for v in vals],
                  reserved_seqs=seqs)
    assert metrics.value("mxnet_ps_deduped_pushes_total") > dup_before
    assert kv.server_stats()[0]["pushes"] == stats0["pushes"]

    net_b = build()
    fit(net_b, "device", update_on_kvstore=True)
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=1e-5, atol=1e-6)

    kv.stop_servers()
    t.join(10)
    assert not t.is_alive()
