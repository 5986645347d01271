"""Fault-tolerance chaos suite (ISSUE 3).

Proves the stack survives the failures SURVEY.md 5.3 only gestured at:
SIGKILL/SIGTERM mid-training resumes to the same loss trajectory,
a truncated checkpoint falls back by checksum, a dead parameter server
fails fast with a rank-naming error (never a hang), a killed dataloader
worker surfaces a structured error, and a stopping/stopped ModelServer
never strands a caller.  The ``test_smoke_*`` subset is the bounded
(~60s) chaos gate ``ci/run.sh tier1`` runs via ``-k smoke``.
"""
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, metrics, retry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.preemption import PreemptionGuard

# spawns subprocesses / in-process multi-thread servers: virtual-CPU-mesh
# territory, skipped under the single-chip ctx-flip
pytestmark = pytest.mark.host_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAOS = os.path.join(REPO, "tests", "chaos_train.py")


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


def _free_port() -> int:
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            eph_lo = int(f.read().split()[0])
    except OSError:
        eph_lo = 32768
    lo, hi = max(10000, eph_lo - 6000), eph_lo - 5
    rng = random.Random()
    for _ in range(64):
        port = rng.randrange(lo, hi)
        s = socket.socket()
        try:
            s.bind(("127.0.0.1", port))
            return port
        except OSError:
            continue
        finally:
            s.close()
    raise RuntimeError("no free port below the ephemeral range")


def _spmd_trainer(seed=0):
    import jax
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    return SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                       {"learning_rate": 0.05},
                       mesh=make_mesh({"dp": 1},
                                      devices=jax.devices()[:1]))


# ---------------------------------------------------------------------------
# fault registry: plan grammar, determinism, metrics
# ---------------------------------------------------------------------------

def test_smoke_plan_parse_and_env(monkeypatch):
    specs = faults.parse_plan(
        "kvstore.recv:p=0.25:kind=timeout:after=2;"
        "checkpoint.write:times=1:seed=7")
    assert [s.site for s in specs] == ["kvstore.recv", "checkpoint.write"]
    assert specs[0].p == 0.25 and specs[0].kind == "timeout" \
        and specs[0].after == 2
    assert specs[1].kind == "error" and specs[1].times == 1
    with pytest.raises(MXNetError, match="unknown fault site"):
        faults.parse_plan("no.such.site:p=1")
    with pytest.raises(MXNetError, match="unknown fault kind"):
        faults.parse_plan("dispatch.op:kind=frobnicate")
    with pytest.raises(MXNetError, match="unknown fault-plan field"):
        faults.parse_plan("dispatch.op:zap=1")
    # env arming — how chaos subprocesses configure the schedule
    monkeypatch.setenv("MXNET_FAULT_PLAN",
                       "serving.execute:p=1:kind=delay:delay_ms=1")
    assert faults.arm_from_env() == 1
    assert faults.armed_sites() == ["serving.execute"]
    faults.disarm()
    # every known site is a real registered name
    assert set(faults.known_sites()) == {
        "checkpoint.write", "kvstore.send", "kvstore.recv",
        "dataloader.worker", "serving.execute", "serving.worker",
        "ps.server", "worker.heartbeat", "dispatch.op", "trainer.step"}


def test_smoke_nan_kind_corrupts_tensor_sites_only():
    import jax.numpy as jnp
    # maybe_corrupt: a firing nan clause poisons the FIRST array
    with faults.fault_plan("trainer.step:kind=nan:times=1"):
        a = jnp.ones((2, 3))
        b = jnp.ones((4,))
        out = faults.maybe_corrupt("trainer.step", [a, b])
        assert bool(jnp.isnan(out[0]).any())
        assert not bool(jnp.isnan(out[1]).any())
        # times=1: the second hit passes clean
        out2 = faults.maybe_corrupt("trainer.step", [a])
        assert not bool(jnp.isnan(out2[0]).any())
    assert metrics.value("mxnet_faults_injected_total",
                         site="trainer.step", kind="nan") >= 1
    # numpy arrays corrupt too (the gluon grad path); the first FLOAT
    # tensor is the target — int token ids are skipped over
    with faults.fault_plan("trainer.step:kind=nan:times=2"):
        f = faults.maybe_corrupt("trainer.step",
                                 [onp.ones(3, "f4")])[0]
        assert onp.isnan(f[0])
        ints, flt = faults.maybe_corrupt(
            "trainer.step", [onp.ones(3, "i4"), onp.ones(3, "f4")])
        assert (ints == 1).all() and onp.isnan(flt[0])
    # bfloat16 (the standard TPU training dtype) IS a float target —
    # numpy refuses to classify ml_dtypes floats, jnp.issubdtype knows
    with faults.fault_plan("trainer.step:kind=nan:times=1"):
        bf = faults.maybe_corrupt(
            "trainer.step", [jnp.ones(3, jnp.bfloat16)])[0]
        assert bool(jnp.isnan(bf).any())
    # a firing nan clause with NOTHING float to corrupt fails loudly
    # (a silent no-injection would make the plan's metrics lie)
    with faults.fault_plan("trainer.step:kind=nan:times=1"):
        with pytest.raises(MXNetError, match="float dtype"):
            faults.maybe_corrupt("trainer.step", [onp.ones(3, "i4")])
    # a tensor-less site rejects kind=nan loudly instead of silently
    # injecting nothing
    with faults.fault_plan("dispatch.op:kind=nan:times=1"):
        with pytest.raises(MXNetError, match="no tensor to corrupt"):
            faults.maybe_fault("dispatch.op")
    # non-nan kinds behave identically through maybe_corrupt
    with faults.fault_plan("trainer.step:kind=error:times=1"):
        with pytest.raises(faults.FaultInjected, match="trainer.step"):
            faults.maybe_corrupt("trainer.step", [onp.ones(2, "f4")])


def test_smoke_seeded_fault_schedule_is_deterministic():
    def schedule(seed):
        spec = faults.FaultSpec("dispatch.op", p=0.3, seed=seed)
        out = []
        for _ in range(200):
            try:
                spec._check({})
                out.append(0)
            except faults.FaultInjected:
                out.append(1)
        return out

    a, b = schedule(11), schedule(11)
    assert a == b                       # same seed -> same schedule
    assert 20 < sum(a) < 100            # p=0.3 actually injects
    assert schedule(12) != a            # seed changes the schedule


def test_smoke_dispatch_fault_and_metrics():
    metrics.reset()
    with faults.fault_plan("dispatch.op:p=1:kind=error:times=1") as fp:
        with pytest.raises(faults.FaultInjected, match="dispatch.op"):
            mx.np.zeros((2,)) + 1
        # times=1: dispatch works again (and the plan context restores)
        (mx.np.zeros((2,)) + 1).asnumpy()
        assert fp.specs[0].injected == 1
    assert not faults._ARMED
    assert metrics.value("mxnet_faults_injected_total",
                         site="dispatch.op", kind="error") == 1
    assert "mxnet_faults_injected_total" in metrics.render_text()


def test_smoke_retry_backoff_deadline_and_metrics():
    metrics.reset()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    assert retry.retry_call(flaky, site="t1", base_ms=1) == "ok"
    assert len(calls) == 3
    assert metrics.value("mxnet_retry_attempts_total", site="t1") == 2

    def always():
        raise ConnectionError("down")

    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        retry.retry_call(always, site="t2", attempts=50, base_ms=20,
                         max_ms=40, deadline_s=0.2)
    assert time.monotonic() - t0 < 2.0   # deadline, not 50 attempts
    assert metrics.value("mxnet_retry_exhausted_total", site="t2") == 1
    # delays grow then cap, jitter stays within [1-j, 1]
    ds = list(retry.backoff_delays(attempts=5, base_ms=100, max_ms=250,
                                   jitter=0.0))
    assert ds == [0.1, 0.2, 0.25, 0.25]


# ---------------------------------------------------------------------------
# checkpoint hardening
# ---------------------------------------------------------------------------

def test_smoke_checkpoint_truncation_falls_back(tmp_path):
    metrics.reset()
    tr = _spmd_trainer()
    mgr = CheckpointManager(str(tmp_path), max_to_keep=3)
    X, Y = mx.np.ones((4, 8)), mx.np.zeros((4, 4))
    tr.step(X, Y)
    mgr.save(tr, step=1)
    ref = [p.data().asnumpy().copy() for p in tr._params]
    tr.step(X, Y)
    mgr.save(tr, step=2)
    # truncate the latest checkpoint (crash mid-write / torn disk)
    with open(str(tmp_path / "ckpt-0000002.params"), "r+b") as f:
        f.truncate(8)
    assert not mgr.verify(2)
    assert mgr.verify(1)
    assert mgr.restore(tr) == 1          # checksum fallback
    for p, r in zip(tr._params, ref):
        onp.testing.assert_allclose(p.data().asnumpy(), r, rtol=1e-6)
    assert metrics.value("mxnet_checkpoint_restore_fallbacks_total") == 1
    assert metrics.value("mxnet_checkpoint_corrupt_total") >= 1
    # an explicitly requested corrupt step refuses loudly
    with pytest.raises(MXNetError, match="SHA-256"):
        mgr.restore(tr, step=2)
    # every checkpoint corrupt -> explicit error, not a silent fresh start
    with open(str(tmp_path / "ckpt-0000001.states"), "r+b") as f:
        f.truncate(4)
    with pytest.raises(MXNetError, match="failed SHA-256"):
        mgr.restore(tr)


def test_smoke_checkpoint_orphan_sweep_and_write_fault(tmp_path):
    metrics.reset()
    old = time.time() - 3600                    # crashed an hour ago
    (tmp_path / "ckpt-staging-abandoned").mkdir()
    (tmp_path / "ckpt-staging-abandoned" / "ckpt.params").write_bytes(b"x")
    (tmp_path / "tmpa1b2c3d4").mkdir()          # pre-hardening staging
    (tmp_path / "ckpt-staging-live").mkdir()    # a CONCURRENT saver's
    (tmp_path / "keepme").mkdir()               # user data: untouched
    for d in ("ckpt-staging-abandoned", "tmpa1b2c3d4", "keepme"):
        os.utime(str(tmp_path / d), (old, old))
    mgr = CheckpointManager(str(tmp_path))
    assert not (tmp_path / "ckpt-staging-abandoned").exists()
    assert not (tmp_path / "tmpa1b2c3d4").exists()
    # fresh staging dir = possibly a live preempted saver: spared
    assert (tmp_path / "ckpt-staging-live").exists()
    assert (tmp_path / "keepme").exists()
    assert metrics.value("mxnet_checkpoint_orphan_sweeps_total") == 2
    (tmp_path / "ckpt-staging-live").rmdir()

    # an injected write fault fails the save loudly, leaves no staging
    # dir behind, and does not corrupt the (empty) manifest
    tr = _spmd_trainer()
    with faults.fault_plan("checkpoint.write:p=1:kind=error:times=1"):
        with pytest.raises(faults.FaultInjected):
            mgr.save(tr, step=1)
    assert mgr.checkpoints == []
    assert not [d for d in os.listdir(str(tmp_path))
                if d.startswith("ckpt-staging-")]
    mgr.save(tr, step=1)                 # clean retry succeeds
    assert mgr.checkpoints == [1]


def test_smoke_checkpoint_prune_tolerates_missing_files(tmp_path):
    tr = _spmd_trainer()
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    for s in (1, 2):
        mgr.save(tr, step=s)
    # step 1's files vanish out from under the manager (operator rm,
    # concurrent cleanup): the next save's prune must not raise
    for f in list(os.listdir(str(tmp_path))):
        if f.startswith("ckpt-0000001."):
            os.remove(str(tmp_path / f))
    mgr.save(tr, step=3)
    assert mgr.checkpoints == [2, 3]
    assert mgr.restore(tr) == 3


# ---------------------------------------------------------------------------
# kvstore_async hardening
# ---------------------------------------------------------------------------

def _start_ps(port, num_workers=1):
    from mxnet_tpu.kvstore_async import run_server
    ev = threading.Event()
    th = threading.Thread(target=run_server, args=(port, num_workers, ev),
                          daemon=True)
    th.start()
    assert ev.wait(20), "parameter server did not come up"
    return th


def _ps_client(monkeypatch, port, num_workers=1):
    from mxnet_tpu.kvstore_async import KVStoreDistAsync
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", str(num_workers))
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    return KVStoreDistAsync()


def test_smoke_kvstore_recv_timeout_fails_fast_naming_rank(monkeypatch):
    metrics.reset()
    port = _free_port()
    _start_ps(port)
    kv = _ps_client(monkeypatch, port)
    try:
        kv.init("w", mx.np.zeros(4))
        with faults.fault_plan("kvstore.recv:p=1:kind=timeout"):
            with pytest.raises(MXNetError,
                               match=r"rank 0/1.*'P'.*timed out.*"
                                     r"MXNET_PS_RECV_TIMEOUT"):
                kv.push("w", mx.np.array(onp.ones(4, "f4")))
        # fail FAST: one bounded wait, no replay doubling the hang
        assert metrics.value("mxnet_faults_injected_total",
                             site="kvstore.recv", kind="timeout") == 1
        assert metrics.value("mxnet_ps_recv_timeouts_total") == 1
        # the acceptance dump: timeout + injection + retry families all
        # in the /metrics-style exposition
        text = metrics.render_text()
        assert "mxnet_ps_recv_timeouts_total 1" in text
        assert "# TYPE mxnet_retry_attempts_total counter" in text
        assert "mxnet_faults_injected_total" in text
        # disarmed: the client reconnects and works again
        kv.push("w", mx.np.array(onp.ones(4, "f4")))
        got = kv.pull("w", out=mx.np.zeros(4)).asnumpy()
        assert got.sum() > 0
    finally:
        kv.stop_servers()


def test_smoke_kvstore_server_restart_midrun_reconnects(monkeypatch):
    metrics.reset()
    port = _free_port()
    th = _start_ps(port)
    kv = _ps_client(monkeypatch, port)
    kv.init("w", mx.np.zeros(4))
    kv.push("w", mx.np.array(onp.ones(4, "f4")))
    kv.stop_servers()
    th.join(10)
    assert not th.is_alive()
    # restart on the same port: the client's next RPC rides the
    # backoff-wrapped reconnect; state is gone, so re-init then push
    th2 = _start_ps(port)
    try:
        kv.init("w", mx.np.zeros(4))
        kv.push("w", mx.np.array(2 * onp.ones(4, "f4")))
        got = kv.pull("w", out=mx.np.zeros(4)).asnumpy()
        onp.testing.assert_allclose(got, 2.0)
        assert metrics.value("mxnet_retry_attempts_total",
                             site="kvstore.rpc") >= 1
    finally:
        kv.stop_servers()
        th2.join(10)


def test_smoke_kvstore_portfile_restart_gets_new_port(monkeypatch,
                                                      tmp_path):
    """Port-file mode (the launcher default): a killed-and-restarted
    server binds a DIFFERENT OS-assigned port and republishes it — the
    client's reconnect must re-resolve from the file, not a cached
    port (the restart advice in the RPC-timeout error depends on
    it)."""
    from mxnet_tpu.kvstore_async import run_server
    monkeypatch.setenv("MXNET_PS_PORT_FILE", str(tmp_path / "port"))
    monkeypatch.setenv("DMLC_SERVER_ID", "0")
    ev = threading.Event()
    th = threading.Thread(target=run_server, args=(0, 1, ev),
                          daemon=True)
    th.start()
    assert ev.wait(20)
    first_port = int((tmp_path / "port.0").read_text())
    kv = _ps_client(monkeypatch, 0)      # base port unused in this mode
    kv.init("w", mx.np.zeros(4))
    kv.push("w", mx.np.array(onp.ones(4, "f4")))
    kv.stop_servers()
    th.join(10)
    ev2 = threading.Event()
    th2 = threading.Thread(target=run_server, args=(0, 1, ev2),
                           daemon=True)
    th2.start()
    assert ev2.wait(20)
    try:
        # almost surely a different port; either way the client must
        # follow the republished file, and state re-seeds cleanly
        kv.init("w", mx.np.zeros(4))
        kv.push("w", mx.np.array(2 * onp.ones(4, "f4")))
        got = kv.pull("w", out=mx.np.zeros(4)).asnumpy()
        onp.testing.assert_allclose(got, 2.0)
        second_port = int((tmp_path / "port.0").read_text())
        assert isinstance(second_port, int) and second_port > 0
        del first_port
    finally:
        kv.stop_servers()
        th2.join(10)


def test_smoke_kvstore_barrier_timeout_names_missing_rank(monkeypatch):
    port = _free_port()
    monkeypatch.setenv("MXNET_PS_BARRIER_TIMEOUT", "1")
    _start_ps(port, num_workers=3)
    kv = _ps_client(monkeypatch, port, num_workers=3)
    try:
        with pytest.raises(MXNetError,
                           match=r"barrier timed out.*1/3.*"
                                 r"\(ranks \[0\]\).*missing ranks "
                                 r"\[1, 2\]"):
            kv.barrier()
    finally:
        kv.stop_servers()


# ---------------------------------------------------------------------------
# serving hardening
# ---------------------------------------------------------------------------

def _model_server(**kw):
    from mxnet_tpu import serving
    from mxnet_tpu.serving import BucketPolicy, ModelServer
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((1, 6), dtype="float32"))
    model = serving.load_served(net)
    return ModelServer(model, policy=BucketPolicy(batch_buckets=(1, 2)),
                       timeout_ms=1.0, **kw)


def test_smoke_serving_execute_fault_recovers():
    srv = _model_server().start()
    try:
        x = onp.ones(6, "f4")
        with faults.fault_plan("serving.execute:p=1:kind=error:times=1"):
            with pytest.raises(faults.FaultInjected,
                               match="serving.execute"):
                srv.infer(x, timeout=10.0)
        # the worker survived the injected batch fault
        assert srv.healthy()
        out = srv.infer(x, timeout=10.0)
        assert out.shape == (3,)
    finally:
        srv.stop()


def test_smoke_serving_stop_fails_inflight_futures():
    srv = _model_server()
    release = threading.Event()
    real_predict = srv.model.predict

    def slow_predict(arrays):
        release.wait(20)
        return real_predict(arrays)

    srv.model.predict = slow_predict
    srv.start()
    try:
        fut = srv.infer_async(onp.ones(6, "f4"))
        deadline = time.monotonic() + 5
        while not srv._inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._inflight, "request never reached the worker"
        srv.stop(timeout=0.3)            # worker is stuck in predict
        with pytest.raises(MXNetError, match="still in flight"):
            fut.result(timeout=5)
    finally:
        release.set()


def test_smoke_serving_worker_death_degrades_healthz():
    from mxnet_tpu.serving.http import make_http_server
    import urllib.error
    import urllib.request

    srv = _model_server()

    def dying_predict(arrays):
        raise SystemExit("worker killed")

    srv.model.predict = dying_predict
    srv.start()
    httpd = make_http_server(srv, port=0)
    http_thread = threading.Thread(target=httpd.serve_forever,
                                   daemon=True)
    http_thread.start()
    try:
        fut = srv.infer_async(onp.ones(6, "f4"))
        # the dying worker fails its in-flight future (no infinite wait)
        with pytest.raises(MXNetError, match="worker thread died"):
            fut.result(timeout=10)
        deadline = time.monotonic() + 5
        while srv.healthy() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not srv.healthy()
        # new submissions fail fast instead of queueing forever
        with pytest.raises(MXNetError, match="degraded"):
            srv.infer_async(onp.ones(6, "f4"))
        # the HTTP health check tells the load balancer
        host, port = httpd.server_address
        try:
            urllib.request.urlopen(f"http://{host}:{port}/healthz",
                                   timeout=10)
            raise AssertionError("healthz should be 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "degraded"
        # ...and inference submits map to 503 (server incapacity), not
        # 400 (caller error) — balancers retry/fail over on 5xx only
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/inference",
            data=json.dumps({"data": [1.0] * 6}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("inference on degraded should be 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["error"] == "degraded"
    finally:
        httpd.shutdown()
        srv.stop(timeout=1.0)


# ---------------------------------------------------------------------------
# dataloader worker death
# ---------------------------------------------------------------------------

class _NpDataset(mx.gluon.data.dataset.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return onp.full((3,), i, dtype="float32")


@pytest.mark.slow    # tier-1 time budget (r8): chaos-smoke (-k smoke, no slow filter) still gates it in tier 1
def test_smoke_dataloader_worker_crash_is_structured(monkeypatch):
    from mxnet_tpu.gluon.data import DataLoader
    # fork: instant workers (pure-numpy dataset) that inherit the armed
    # plan; kind=crash is os._exit in the worker — the killed-worker
    # case without racing os.kill
    monkeypatch.setenv("MXNET_DATALOADER_START_METHOD", "fork")
    faults.arm("dataloader.worker", kind="crash", times=1)
    dl = DataLoader(_NpDataset(), batch_size=4, num_workers=1, timeout=8)
    with pytest.raises(MXNetError, match="worker process likely died"):
        list(dl)
    faults.disarm()

    # kind=error propagates the structured exception through the pool
    faults.arm("dataloader.worker", kind="error", times=1)
    dl2 = DataLoader(_NpDataset(), batch_size=4, num_workers=1,
                     timeout=30)
    with pytest.raises(faults.FaultInjected, match="dataloader.worker"):
        list(dl2)
    faults.disarm()

    # healthy loader still delivers everything
    dl3 = DataLoader(_NpDataset(), batch_size=4, num_workers=1,
                     timeout=30)
    assert sum(b.shape[0] for b in dl3) == 16


# ---------------------------------------------------------------------------
# preemption + trainer loops
# ---------------------------------------------------------------------------

def test_smoke_preemption_guard_flag_and_restore():
    with PreemptionGuard() as guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5
        while not guard.requested and time.monotonic() < deadline:
            time.sleep(0.01)
        assert guard.requested
        assert guard.signal_name == "SIGTERM"
    assert metrics.value("mxnet_preemption_signals_total",
                         signal="SIGTERM") >= 1
    # handlers restored: the default SIGTERM handler is back
    assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL,
                                                signal.default_int_handler,
                                                signal.Handlers.SIG_DFL)


def test_smoke_preemption_second_signal_escalates():
    """The escalation contract: signal one sets the cooperative flag;
    signal two must still kill a wedged loop — SystemExit(128+sig) for
    SIGTERM (default prior handler), KeyboardInterrupt for SIGINT, and
    a callable prior handler runs instead when one was installed."""
    with PreemptionGuard(signals=(signal.SIGTERM,)) as guard:
        signal.raise_signal(signal.SIGTERM)
        assert guard.requested
        with pytest.raises(SystemExit) as ei:
            signal.raise_signal(signal.SIGTERM)
        assert ei.value.code == 128 + int(signal.SIGTERM)
    # SIGINT escalates to KeyboardInterrupt (the Ctrl-C-twice contract;
    # python's default SIGINT handler is callable, raising it)
    with PreemptionGuard(signals=(signal.SIGINT,)) as guard:
        signal.raise_signal(signal.SIGINT)
        assert guard.requested
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)
    # a custom prior handler wins on the second signal
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with PreemptionGuard(signals=(signal.SIGTERM,)) as guard:
            signal.raise_signal(signal.SIGTERM)
            assert guard.requested and not seen
            signal.raise_signal(signal.SIGTERM)
            assert seen == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_smoke_spmd_fit_resume_is_idempotent(tmp_path):
    def batch_fn(step):
        rng = onp.random.RandomState(100 + step)
        return (mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4")))

    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    tr = _spmd_trainer()
    loss = tr.fit(batch_fn, 4, checkpoint_manager=mgr, checkpoint_every=2)
    assert tr._step_count == 4 and mgr.latest_step == 4
    ref = float(loss.asnumpy())
    w_ref = tr._params[0].data().asnumpy().copy()

    # a rerun of a completed fit is a no-op
    assert tr.fit(batch_fn, 4, checkpoint_manager=mgr) is None
    assert tr._step_count == 4

    # a FRESH trainer (different init) resumes and lands identically
    tr2 = _spmd_trainer(seed=99)
    loss2 = tr2.fit(batch_fn, 5, checkpoint_manager=mgr,
                    checkpoint_every=2)
    assert tr2._step_count == 5
    # ...and matches a never-interrupted 5-step run exactly
    tr3 = _spmd_trainer()
    loss3 = tr3.fit(batch_fn, 5)
    onp.testing.assert_allclose(float(loss2.asnumpy()),
                                float(loss3.asnumpy()),
                                rtol=1e-6)
    del ref, w_ref

    # an iterable batch source that runs dry fails structured, not with
    # a bare StopIteration
    short = [batch_fn(i) for i in range(2)]
    with pytest.raises(MXNetError, match="exhausted at step 2"):
        tr3.fit(short, 9)


def test_estimator_fit_checkpoint_resume_and_preemption(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import BatchEnd

    rng = onp.random.RandomState(3)
    data = [(mx.np.array(rng.uniform(-1, 1, (4, 6)).astype("f4")),
             mx.np.array(rng.uniform(-1, 1, (4, 3)).astype("f4")))
            for _ in range(8)]

    def build():
        mx.random.seed(5)
        net = mx.gluon.nn.Dense(3)
        net.initialize()
        net(mx.np.zeros((1, 6)))
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.05})
        return net, Estimator(net, mx.gluon.loss.L2Loss(), trainer=tr)

    mgr = CheckpointManager(str(tmp_path / "a"), max_to_keep=2)
    net, est = build()
    est.fit(data, batches=3, checkpoint_manager=mgr, checkpoint_every=1)
    assert est.trainer._optimizer.num_update == 3
    assert mgr.latest_step == 3
    w3 = net.weight.data().asnumpy().copy()

    # rerun-to-done: no-op (batches counts TOTAL steps across restarts)
    est.fit(data, batches=3, checkpoint_manager=mgr)
    assert est.trainer._optimizer.num_update == 3
    onp.testing.assert_allclose(net.weight.data().asnumpy(), w3)

    # fresh process analog: new net+trainer, same manager -> continues
    net2, est2 = build()
    est2.fit(data, batches=5, checkpoint_manager=mgr, checkpoint_every=1)
    assert est2.trainer._optimizer.num_update == 5
    assert mgr.latest_step == 5

    # preemption mid-fit: SIGTERM after the 2nd batch -> the in-flight
    # batch finishes, a checkpoint lands, fit returns cleanly
    class _Preempt(BatchEnd):
        def batch_end(self, estimator, *a, **kw):
            if estimator.trainer._optimizer.num_update == 2:
                os.kill(os.getpid(), signal.SIGTERM)
            return False

    mgr2 = CheckpointManager(str(tmp_path / "b"), max_to_keep=2)
    net3, est3 = build()
    est3.fit(data, batches=8, checkpoint_manager=mgr2,
             event_handlers=[_Preempt()])
    assert est3.trainer._optimizer.num_update < 8
    assert mgr2.latest_step == est3.trainer._optimizer.num_update
    # restart finishes the job
    net4, est4 = build()
    est4.fit(data, batches=8, checkpoint_manager=mgr2)
    assert est4.trainer._optimizer.num_update == 8


# ---------------------------------------------------------------------------
# subprocess chaos: SIGKILL / SIGTERM mid-training
# ---------------------------------------------------------------------------

def _chaos_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("MXNET_FAULT_PLAN", None)
    env.pop("MXNET_CHAOS_STEP_DELAY", None)
    env.update(extra)
    return env


def _run_chaos(ckdir, out, steps, ready=None, env=None):
    args = [sys.executable, CHAOS, str(ckdir), str(out), str(steps)]
    if ready:
        args.append(str(ready))
    return subprocess.Popen(args, env=env or _chaos_env())


def _wait_file(path, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(str(path)):
            return True
        time.sleep(0.05)
    return False


@pytest.fixture(scope="module")
def uninterrupted_loss(tmp_path_factory):
    """Final loss of a never-interrupted 6-step reference run."""
    d = tmp_path_factory.mktemp("chaos-ref")
    out = d / "out.json"
    p = _run_chaos(d / "ck", out, 6)
    assert p.wait(240) == 0
    payload = json.loads(out.read_text())
    assert payload["step_count"] == 6
    return payload["final_loss"]


@pytest.mark.slow
def test_chaos_sigkill_midrun_resumes_to_same_loss(tmp_path,
                                                   uninterrupted_loss):
    ck, out, ready = tmp_path / "ck", tmp_path / "out.json", \
        tmp_path / "ready"
    p = _run_chaos(ck, out, 6, ready=ready,
                   env=_chaos_env(MXNET_CHAOS_STEP_DELAY="0.4"))
    assert _wait_file(ready), "run never reached step 1"
    p.send_signal(signal.SIGKILL)        # no warning, no cleanup
    assert p.wait(60) != 0
    assert not out.exists()              # died before finishing
    ckmgr = CheckpointManager(str(ck))
    resumed_from = ckmgr.latest_step
    assert resumed_from is not None and 1 <= resumed_from < 6
    # rerun THE SAME command: auto-resume completes the job
    p2 = _run_chaos(ck, out, 6)
    assert p2.wait(240) == 0
    payload = json.loads(out.read_text())
    assert payload["step_count"] == 6
    # same seed, same per-step batches -> same trajectory (fp-exact ops;
    # tolerance covers accumulation-order wiggle, documented in
    # docs/fault_tolerance.md)
    onp.testing.assert_allclose(payload["final_loss"],
                                uninterrupted_loss, rtol=1e-5)


@pytest.mark.slow
def test_chaos_sigterm_checkpoints_and_exits_cleanly(tmp_path,
                                                     uninterrupted_loss):
    ck, out, ready = tmp_path / "ck", tmp_path / "out.json", \
        tmp_path / "ready"
    p = _run_chaos(ck, out, 6, ready=ready,
                   env=_chaos_env(MXNET_CHAOS_STEP_DELAY="0.4"))
    assert _wait_file(ready), "run never reached step 1"
    p.send_signal(signal.SIGTERM)
    assert p.wait(120) == 0              # GRACEFUL: clean exit code
    payload = json.loads(out.read_text())
    done = payload["step_count"]
    assert 1 <= done < 6                 # preempted partway
    # the in-flight step was finished and checkpointed before exit
    assert CheckpointManager(str(ck)).latest_step == done
    out.unlink()
    p2 = _run_chaos(ck, out, 6)
    assert p2.wait(240) == 0
    payload = json.loads(out.read_text())
    assert payload["step_count"] == 6
    onp.testing.assert_allclose(payload["final_loss"],
                                uninterrupted_loss, rtol=1e-5)
