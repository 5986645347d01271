"""SPMD mesh training on the 8-device virtual CPU mesh (reference analog:
tests/nightly/dist_sync_kvstore.py — push/pull invariants — translated to
mesh collectives per SURVEY.md section 4)."""
import jax
import numpy as onp
import pytest
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (DATA_PARALLEL_RULES,
                                DEFAULT_TRANSFORMER_RULES, PartitionRules,
                                SPMDTrainer, make_mesh, shard_batch)
from mxnet_tpu.test_utils import assert_almost_equal

# chip ctx-flip: this whole file needs the multi-device virtual
# CPU mesh (see conftest host_mesh marker)
pytestmark = pytest.mark.host_mesh


def _devices(n):
    return jax.devices()[:n]


def test_make_mesh_shapes():
    mesh = make_mesh({"dp": 2, "tp": 4})
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.devices.shape == (2, 4)
    mesh2 = make_mesh({"dp": -1, "tp": 2})
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(mx.MXNetError):
        make_mesh({"dp": 3, "tp": 3})


def test_shard_batch_placement():
    mesh = make_mesh({"dp": 8})
    x = mx.np.ones((16, 4))
    xs = shard_batch(x, mesh)
    assert len(xs._data.devices()) == 8
    assert xs.shape == (16, 4)


def test_partition_rules_filtering():
    mesh = make_mesh({"dp": 2, "tp": 4})
    rules = PartitionRules([(r"weight$", P("tp", None))])
    # divisible dim -> sharded
    assert rules.spec_for("dense.weight", (8, 3), mesh) == P("tp", None)
    # non-divisible dim -> dropped to replicated
    assert rules.spec_for("dense.weight", (6, 3), mesh) == P(None, None)
    # no match -> replicated
    assert rules.spec_for("dense.bias", (8,), mesh) == P()


def test_dp_training_matches_single_device():
    """Data-parallel over 8 devices must equal single-device training —
    the reference's kvstore invariant (pulled == sum of pushes)."""
    def build():
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
        net.initialize()
        return net

    X = onp.random.RandomState(0).uniform(-1, 1, (16, 8)).astype("float32")
    Y = onp.random.RandomState(1).randint(0, 4, (16,)).astype("int32")
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    results = []
    for ndev in (1, 8):
        net = build()
        mesh = make_mesh({"dp": ndev}, devices=_devices(ndev))
        tr = SPMDTrainer(net, loss_fn, "sgd",
                         {"learning_rate": 0.1}, mesh=mesh,
                         rules=DATA_PARALLEL_RULES)
        for _ in range(3):
            loss = tr.step(mx.np.array(X), mx.np.array(Y))
        results.append((float(loss.asnumpy()),
                        [p.data().asnumpy()
                         for p in net.collect_params().values()]))

    (l1, p1), (l8, p8) = results
    assert abs(l1 - l8) < 1e-5
    for a, b in zip(p1, p8):
        assert_almost_equal(a, b, rtol=1e-5, atol=1e-6)


def test_tp_training_matches_replicated():
    """Tensor-parallel sharded params must train to the same values as
    fully-replicated — validates the Megatron rules produce identical
    math, just sharded."""
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderLayer

    def build():
        mx.random.seed(11)
        layer = BERTEncoderLayer(units=32, hidden_size=64, num_heads=4,
                                 dropout=0.0)
        layer.initialize()
        layer(mx.np.zeros((2, 8, 32)))  # settle shapes
        return layer

    X = onp.random.RandomState(2).uniform(-1, 1, (4, 8, 32)).astype("float32")
    Y = onp.random.RandomState(3).randint(0, 32, (4, 8)).astype("int32")
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)

    outs = []
    for rules, mesh_shape in ((DATA_PARALLEL_RULES, {"dp": 1}),
                              (DEFAULT_TRANSFORMER_RULES,
                               {"dp": 2, "tp": 4})):
        layer = build()
        mesh = make_mesh(mesh_shape, devices=_devices(
            2 * 4 if "tp" in mesh_shape else 1))
        tr = SPMDTrainer(layer, loss_fn, "sgd", {"learning_rate": 0.05},
                         mesh=mesh, rules=rules)
        for _ in range(2):
            loss = tr.step(mx.np.array(X), mx.np.array(Y))
        outs.append(float(loss.asnumpy()))
        # verify qkv weight actually sharded in the tp run
        if "tp" in mesh_shape:
            qkv = layer.attn_qkv.weight.data()._data
            assert len(qkv.devices()) == 8
    assert abs(outs[0] - outs[1]) < 1e-4


def test_sp_sequence_sharding_runs():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderLayer
    mx.random.seed(5)
    layer = BERTEncoderLayer(units=16, hidden_size=32, num_heads=2,
                             dropout=0.0)
    layer.initialize()
    layer(mx.np.zeros((2, 8, 16)))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    tr = SPMDTrainer(layer, loss_fn, "adamw", {"learning_rate": 1e-3},
                     mesh=mesh, rules=DEFAULT_TRANSFORMER_RULES,
                     data_spec=P("dp", "sp"), label_spec=P("dp", "sp"))
    X = onp.random.uniform(-1, 1, (4, 8, 16)).astype("float32")
    Y = onp.random.randint(0, 16, (4, 8)).astype("int32")
    l1 = float(tr.step(mx.np.array(X), mx.np.array(Y)).asnumpy())
    l2 = float(tr.step(mx.np.array(X), mx.np.array(Y)).asnumpy())
    assert onp.isfinite(l1) and onp.isfinite(l2)
    assert l2 < l1  # optimizing


@pytest.mark.slow    # tier-1 time budget (r8): ci/run.sh dryrun runs __graft_entry__.py itself
def test_graft_entry_hooks():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out[0].shape[0] == 2
    ge.dryrun_multichip(8)


def test_kvstore_local_push_pull():
    kv = mx.kvstore.create("local")
    kv.init(3, mx.np.ones((2, 2)))
    kv.push(3, mx.np.full((2, 2), 4.0))
    out = mx.np.zeros((2, 2))
    kv.pull(3, out=out)
    assert out.asnumpy().sum() == 16.0
    # multi-device gradient list reduces (CommDevice analog)
    kv.push(3, [mx.np.ones((2, 2)), mx.np.ones((2, 2))])
    kv.pull(3, out=out)
    assert out.asnumpy().sum() == 8.0


def test_kvstore_dist_async_guidance(monkeypatch):
    """Outside a launched job (no DMLC env) dist_async explains how to
    start the parameter service instead of hanging on a connect."""
    monkeypatch.delenv("DMLC_PS_ROOT_PORT", raising=False)
    monkeypatch.delenv("DMLC_ROLE", raising=False)
    with pytest.raises(mx.MXNetError, match="launch.py -n 2 -s 1"):
        mx.kvstore.create("dist_async")


def test_kvstore_dist_async_service(monkeypatch):
    """The host-side parameter service end-to-end in one process: a real
    TCP server thread, a client created via mx.kv.create('dist_async') —
    init / running-sum push / pull, server-side optimizer updates applied
    per push (Hogwild), barrier, stats, stop."""
    import socket
    import threading
    import numpy as onp
    from mxnet_tpu import kvstore_async as ka

    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    ready = threading.Event()
    t = threading.Thread(target=ka.run_server, args=(port, 1, ready),
                         daemon=True)
    t.start()
    assert ready.wait(10)

    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    kv = mx.kvstore.create("dist_async")
    assert kv.type == "dist_async"
    assert kv.rank == 0 and kv.num_workers == 1

    # running-sum mode (no server-side optimizer)
    kv.init("w", mx.np.zeros((2, 3)))
    kv.push("w", mx.np.ones((2, 3)))
    kv.push("w", mx.np.ones((2, 3)) * 2)
    onp.testing.assert_allclose(kv.pull("w").asnumpy(), 3.0)

    # server-side optimizer: push applies sgd immediately
    kv.init("p", mx.np.ones((4,)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5))
    kv.push("p", mx.np.ones((4,)))          # p <- p - 0.5 * 1
    onp.testing.assert_allclose(kv.pull("p").asnumpy(), 0.5, atol=1e-6)
    kv.push("p", mx.np.ones((4,)))
    onp.testing.assert_allclose(kv.pull("p").asnumpy(), 0.0, atol=1e-6)

    kv.barrier()                            # 1-worker barrier: immediate
    stats = kv.server_stats()
    assert stats[0]["pushes"] == 4 and "p" in stats[0]["keys"]

    # live hyperparam updates reach the server WITHOUT resetting state:
    # momentum built at lr=0.5 must persist across the lr change
    kv.init("q", mx.np.zeros((2,)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5,
                                         momentum=0.5))
    kv.push("q", mx.np.ones((2,)))     # m=1, q = -0.5
    kv.update_optimizer_params({"learning_rate": 0.1})
    kv.push("q", mx.np.ones((2,)))     # m=1.5, q = -0.5 - 0.1*1.5
    onp.testing.assert_allclose(kv.pull("q").asnumpy(), -0.65, atol=1e-6)

    # optimizer-state round trip over the wire (momentum survives)
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".states") as f:
        kv.save_optimizer_states(f.name)
        kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                             momentum=0.5))   # resets m
        kv.load_optimizer_states(f.name)
    kv.push("q", mx.np.zeros((2,)))    # m = 0.5*1.5 -> q -= 0.1*0.75
    onp.testing.assert_allclose(kv.pull("q").asnumpy(), -0.725, atol=1e-6)

    # multi-key batched push/pull (one frame per server)
    kv.init([f"mk{i}" for i in range(5)],
            [mx.np.zeros((3,)) for _ in range(5)])
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=1.0))
    kv.push([f"mk{i}" for i in range(5)],
            [mx.np.ones((3,)) * i for i in range(5)])
    outs = kv.pull([f"mk{i}" for i in range(5)])
    for i, o in enumerate(outs):
        onp.testing.assert_allclose(o.asnumpy(), -float(i), atol=1e-6)

    # server errors come back as MXNetError, connection stays usable
    with pytest.raises(mx.MXNetError, match="uninitialized"):
        kv.push("never_inited", mx.np.ones((1,)))
    onp.testing.assert_allclose(kv.pull("q").asnumpy(), -0.725, atol=1e-6)

    # compression on the async wire (r4): packed push payloads with
    # per-worker error feedback; bad codec names still refused
    kv.set_gradient_compression({"type": "2bit", "threshold": 1.0})
    kv.init("c", mx.np.zeros((8,)))
    before = kv.push_wire_bytes
    kv.push("c", mx.np.ones((8,)))          # 8 codes pack into 2 bytes
    assert kv.push_wire_bytes - before == 2
    with pytest.raises(mx.MXNetError, match="compression type"):
        kv.set_gradient_compression({"type": "bogus"})
    kv.set_gradient_compression({"type": "none"})

    kv.stop_servers()
    t.join(10)
    assert not t.is_alive()


def test_kvstore_dist_async_needs_servers(monkeypatch):
    """A launched job without -s (DMLC_NUM_SERVER=0) gets the guidance
    error, not a ZeroDivisionError from key hashing."""
    monkeypatch.setenv("DMLC_ROLE", "worker")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", "9876")
    monkeypatch.setenv("DMLC_NUM_SERVER", "0")
    with pytest.raises(mx.MXNetError, match="-s 1"):
        mx.kvstore.create("dist_async")


def test_trainer_update_on_kvstore_matches_local():
    """update_on_kvstore=True (the dist_async/server-side mode) must
    produce the same trajectory as the local update path for the same
    optimizer on a single process (reference trainer.py contract)."""
    import numpy as onp
    mx.random.seed(0)
    def build():
        net = nn.Dense(2, in_units=3)
        net.initialize()
        net(mx.np.zeros((1, 3)))
        return net
    net_a, net_b = build(), build()
    # identical inits
    net_b.weight.set_data(net_a.weight.data().copy())
    net_b.bias.set_data(net_a.bias.data().copy())
    tr_a = mx.gluon.Trainer(net_a.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device", update_on_kvstore=False)
    tr_b = mx.gluon.Trainer(net_b.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device", update_on_kvstore=True)
    loss_fn = mx.gluon.loss.L2Loss()
    rng = onp.random.RandomState(5)
    for _ in range(4):
        x = mx.np.array(rng.uniform(-1, 1, (4, 3)).astype("float32"))
        y = mx.np.array(rng.uniform(-1, 1, (4, 2)).astype("float32"))
        for net, tr in ((net_a, tr_a), (net_b, tr_b)):
            with mx.autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(4)
    onp.testing.assert_allclose(net_a.weight.data().asnumpy(),
                                net_b.weight.data().asnumpy(),
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(net_a.bias.data().asnumpy(),
                                net_b.bias.data().asnumpy(),
                                rtol=1e-5, atol=1e-6)


def test_spmd_batchnorm_running_stats_advance():
    """BN running stats must update inside the jitted SPMD step (the
    reference updates them as a stateful side effect of the cached graph)
    and must NOT receive optimizer updates (wd would decay them)."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
            nn.BatchNorm(in_channels=4), nn.Activation("relu"))
    dense = nn.Dense(2)
    net.add(dense)
    net.initialize()
    net(mx.np.zeros((1, 3, 8, 8)))

    # lr=0 freezes weights so per-step batch stats are constant and the
    # momentum recursion is exact; a (wrong) optimizer update on the
    # stats would still show as momentum-buffer drift in later steps
    mesh = make_mesh({"dp": 2}, devices=_devices(2))
    tr = SPMDTrainer(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.0,
                                       "momentum": 0.9, "wd": 0.1},
                     mesh=mesh, rules=DATA_PARALLEL_RULES)
    bn = net[1]
    rm0 = onp.asarray(bn.running_mean.data()._data).copy()
    rv0 = onp.asarray(bn.running_var.data()._data).copy()
    assert (rm0 == 0).all() and (rv0 == 1).all()

    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.uniform(1.0, 2.0, (8, 3, 8, 8)).astype("float32"))
    y = mx.np.array(rng.randint(0, 2, (8,)).astype("int32"))
    for _ in range(3):
        tr.step(x, y)

    rm = onp.asarray(bn.running_mean.data()._data)
    rv = onp.asarray(bn.running_var.data()._data)
    assert not onp.allclose(rm, 0.0)
    assert not onp.allclose(rv, 1.0)
    # exact momentum recursion: stats after K steps with constant batch
    # stats m_b: rm = (1 - 0.9**K) * m_b  — verified against an eager
    # forward's batch stats (and in particular NO wd decay applied)
    conv_out = net[0](x)
    m_b = onp.asarray(conv_out._data).mean(axis=(0, 2, 3))
    v_b = onp.asarray(conv_out._data).var(axis=(0, 2, 3))
    assert_almost_equal(rm, (1 - 0.9 ** 3) * m_b, rtol=2e-2, atol=2e-4)
    assert_almost_equal(rv, (1 - 0.9 ** 3) * v_b + 0.9 ** 3 * 1.0,
                        rtol=2e-2, atol=2e-4)


def test_spmd_step_loss_matches_eager_with_bn():
    """SPMD jitted step loss == eager Trainer loss for a BN net (the
    mutated-state plumbing must not disturb the loss/grad path)."""
    mx.random.seed(7)
    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=6), nn.BatchNorm(axis=-1,
                                                      in_channels=8),
                nn.Activation("relu"), nn.Dense(3, in_units=8))
        net.initialize()
        return net
    net_a = build()
    mx.random.seed(7)
    net_b = build()

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 2}, devices=_devices(2))
    tr = SPMDTrainer(net_a, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=mesh, rules=DATA_PARALLEL_RULES)

    from mxnet_tpu import autograd
    trainer_b = mx.gluon.Trainer(net_b.collect_params(), "sgd",
                                 {"learning_rate": 0.05})
    rng = onp.random.RandomState(3)
    for step in range(2):
        x_np = rng.uniform(-1, 1, (8, 6)).astype("float32")
        y_np = rng.randint(0, 3, (8,)).astype("int32")
        la = float(tr.step(mx.np.array(x_np), mx.np.array(y_np)).asnumpy())
        with autograd.record():
            out = net_b(mx.np.array(x_np))
            # per-sample loss + step(batch) — the gluon convention; the
            # SPMD step differentiates the MEAN loss, so effective grads
            # match (sum/batch == mean)
            lb = loss_fn(out, mx.np.array(y_np))
        lb.backward()
        trainer_b.step(8)
        assert_almost_equal(la, float(lb.mean().asnumpy()),
                            rtol=1e-4, atol=1e-5)
    # running stats advanced identically on both paths
    assert_almost_equal(net_a[1].running_mean.data(),
                        net_b[1].running_mean.data(), rtol=1e-4, atol=1e-6)


def test_hybrid_multislice_mesh():
    """make_mesh(slices=S) builds the DCN x ICI hybrid layout (SURVEY
    5.8, jax create_hybrid_device_mesh analog): the dcn axis is
    slice-major — its high-order factor walks slices, its low-order
    remainder and every other axis stay within a slice — and training
    over it works end to end."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import (make_mesh, slice_groups,
                                    PartitionRules, SPMDTrainer)

    devs = jax.devices()[:8]
    mesh = make_mesh({"dp": 4, "tp": 2}, devices=devs, slices=2,
                     dcn_axis="dp")
    assert mesh.shape == {"dp": 4, "tp": 2}
    # virtual CPU reports no slice structure -> contiguous halves stand
    # in for slices; dp rows 0-1 must be slice 0, rows 2-3 slice 1
    half0 = {d.id for d in devs[:4]}
    assert {d.id for d in mesh.devices[:2, :].ravel()} == half0
    assert {d.id for d in mesh.devices[2:, :].ravel()} == \
        {d.id for d in devs[4:]}
    # each tp pair (ICI neighbors) stays inside one slice
    for i in range(4):
        row = {d.id for d in mesh.devices[i, :]}
        assert row <= half0 or not (row & half0)

    # validation errors
    with pytest.raises(mx.MXNetError, match="divide"):
        make_mesh({"dp": 3, "tp": 2}, devices=devs[:6], slices=2)
    with pytest.raises(mx.MXNetError, match="not a mesh axis"):
        make_mesh({"dp": 4, "tp": 2}, devices=devs, slices=2,
                  dcn_axis="pp")

    # slice_groups fallback: one group when nothing reports slices
    gs = slice_groups(devs)
    assert len(gs) >= 1

    # end-to-end: dp over dcn x ici, tp inside a slice
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(16, in_units=8, activation="relu"),
            mx.gluon.nn.Dense(4, in_units=16))
    net.initialize()
    rules = PartitionRules([
        (r"0\.weight$", P("tp", None)),
        (r"0\.bias$", P("tp")),
        (r"1\.weight$", P(None, "tp")),
    ])
    tr = SPMDTrainer(net, mx.gluon.loss.L2Loss(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     mesh=mesh, rules=rules,
                     data_spec=P("dp"), label_spec=P("dp"))
    import numpy as onp
    rng = onp.random.RandomState(1)
    x = rng.uniform(-1, 1, (8, 8)).astype("float32")
    y = rng.uniform(-1, 1, (8, 4)).astype("float32")
    l1 = float(tr.step(mx.np.array(x), mx.np.array(y)).asnumpy())
    l2 = float(tr.step(mx.np.array(x), mx.np.array(y)).asnumpy())
    assert l2 < l1


# ---------------------------------------------------------------------------
# the trainer step's own spans (ISSUE 26)
# ---------------------------------------------------------------------------

def _dense_trainer():
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    tr = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                     {"learning_rate": 0.05},
                     mesh=make_mesh({"dp": 1}, devices=_devices(1)))

    def batch(step):
        rng = onp.random.RandomState(step)
        return (mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4")))
    return tr, batch


def test_the_train_step_is_in_the_program_table_and_its_scopes_read():
    """SPMDTrainer's compiled step has a line of role ``train_step``;
    its instructions resolve to ``loss`` and ``optim`` (a Dense net has
    no other component), read by lowering the step again from the
    shapes noted when it was traced, the parameters on the one-device
    mesh they came in on."""
    from mxnet_tpu import tracing
    tracing.reset()
    tr, batch = _dense_trainer()
    for i in range(3):
        tr.step(*batch(i))
    rec, = [p for p in tracing.programs() if p.role == "train_step"]
    assert rec.module == "jit_step" and rec.attrs["devices"] >= 1
    assert rec.attrs["optimizer"] and rec.family
    built, seconds = len(rec.built()), rec.seconds()
    assert built >= 1 and rec.seconds("trace", "lower") > 0
    scopes = rec.scopes()
    if rec.attrs["devices"] > 1:
        assert scopes is None       # a real mesh: until a cell wants it
        return
    comps = {c for c, _, _ in scopes.values()}
    assert {"loss", "optim"} <= comps <= {"loss", "optim", "unscoped"}
    assert {d for c, _, d in scopes.values() if c == "loss"} == {
        "fwd", "bwd"}
    # what the reading built (the batch came in committed to the
    # device, which the noted shapes cannot say, so jax lowers again
    # and, where it has a persistent cache, loads) is in no sum
    assert len(rec.built()) == built and rec.seconds() == seconds
    assert all(e.get("reading") for e in rec.shapes[built:])


def test_step_spans_for_a_bare_loop_and_inside_fit():
    """A caller's own loop over trainer.step() records spmd.step >
    step.place, step.dispatch; inside fit() the same three nest under
    train.step, once."""
    from mxnet_tpu import tracing
    tracing.configure(sample=1.0)
    try:
        tr, batch = _dense_trainer()
        for i in range(2):
            tr.step(*batch(i))
        roots = [r for r in tracing.spans() if r["name"] == "spmd.step"]
        assert [r["attrs"]["step"] for r in roots] == [0, 1]
        for root in roots:
            assert root["parent_id"] == ""
            kids = [r for r in tracing.spans(root["trace_id"])
                    if r is not root]
            # a step that builds its program also holds the build's
            # stages, under the dispatch that caused them
            built = [k for k in kids if k["name"].startswith("program.")]
            kids = [k for k in kids if k not in built]
            assert sorted(k["name"] for k in kids) == ["step.dispatch",
                                                       "step.place"]
            assert all(k["parent_id"] == root["span_id"] for k in kids)
            dispatch, = [k for k in kids if k["name"] == "step.dispatch"]
            assert all(b["parent_id"] == dispatch["span_id"]
                       for b in built)
        first = {r["name"] for r in tracing.spans(roots[0]["trace_id"])
                 if r["attrs"].get("role") == "train_step"}
        assert {"program.trace", "program.lower"} < first

        tracing.reset()
        tr.fit(batch, 5)
        steps = [r for r in tracing.spans() if r["name"] == "train.step"]
        assert len(steps) == 3
        for step in steps:
            names = sorted(r["name"]
                           for r in tracing.spans(step["trace_id"]))
            assert names == ["spmd.step", "step.dispatch", "step.place",
                             "train.step"], names
            inner, = [r for r in tracing.spans(step["trace_id"])
                      if r["name"] == "spmd.step"]
            assert inner["parent_id"] == step["span_id"]

        tracing.configure(sample=0)
        tr.step(*batch(9))
        assert tracing.spans() == []
    finally:
        tracing.configure()
