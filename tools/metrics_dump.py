"""Run a small workload and print the runtime metrics exposition.

The smoke-test entry point for the metrics subsystem
(``mxnet_tpu/metrics.py``): drives a real workload through the
instrumented layers (dispatch, engine, collectives, training loop) and
prints what the registry saw — Prometheus text by default, JSON with
``--format json``.

    python tools/metrics_dump.py --workload resnet_step
    python tools/metrics_dump.py --workload mlp_fit --format json

Workloads:
  resnet_step  ResNet-18 SPMDTrainer steps (compiled train step; shows
               compile misses, step-phase histograms, dispatch counters
               from the eager settle forward).
  mlp_fit      tiny MLP through the gluon estimator fit loop (eager
               dispatch per op, kvstore push, data/dispatch/sync split).
  eager        a handful of eager ops + a waitall (dispatch and engine
               counters only).
  bulk         an eager training micro-loop exercising the lazy
               bulking engine: segment flush reasons, segment-cache
               hits/misses, and the ops-per-segment histogram.
  health       an SPMD micro-fit under a seeded NaN fault plan with a
               HealthGuard: health event counters, skip totals, the
               loss EMA gauge, and the fused-check latency histogram.
  input        a prefetched SPMD micro-fit with a deliberately slow
               host loader: prefetch queue depth, per-batch H2D
               seconds, per-step stall seconds (the input-pipeline
               number of record), batch/invalidated counters.
  resilience   a replicated ModelServer plus a supervised
               GenerationServer under seeded worker-kill / decode-fault
               plans: recovery counters (by site), recovered tokens,
               recovery latency, worker restarts, breaker gauge.
  dist-comm    an update-heavy adam micro-fit through the bucketed,
               priority-scheduled, overlapped gradient-reduction
               scheduler on a synthetic-slow wire: buckets dispatched,
               per-bucket comm latency vs exposed wait, the per-round
               overlap fraction, and compressed-vs-raw wire bytes
               (second fit under 2bit error feedback).
  trace        a traced generation workload (MXNET_TRACE_SAMPLE=1):
               the serving/generation latency histograms record the
               trace id of their slowest recent observation — the
               ``exemplar`` field in the JSON exposition links a bad
               histogram straight to the trace that caused it (use
               ``--format json``; the Prometheus text is unchanged).

Runs on the CPU backend by default so it works anywhere (pass
``--platform ambient`` to keep the environment's backend, e.g. the TPU
under the chip tool).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _workload_resnet_step(steps: int) -> None:
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.gluon.model_zoo import vision as zoo
    from mxnet_tpu.parallel import (SPMDTrainer, make_mesh,
                                    DATA_PARALLEL_RULES)

    mx.random.seed(0)
    net = zoo.get_model("resnet18_v1", classes=10)
    net.initialize()
    net(mx.np.zeros((1, 3, 32, 32), dtype="float32"))   # eager settle
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = SPMDTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1},
        mesh=mesh, rules=DATA_PARALLEL_RULES)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.uniform(-1, 1, (4, 3, 32, 32)).astype("float32"))
    y = mx.np.array(rng.randint(0, 10, (4,)).astype("int32"))
    for _ in range(steps):
        loss = trainer.step(x, y)       # records data/dispatch phases
        t1 = time.perf_counter()
        loss.asnumpy()                  # device sync
        metrics.STEP_SYNC_SECONDS.observe(time.perf_counter() - t1)
    mx.waitall()


def _workload_mlp_fit(steps: int) -> None:
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.estimator import Estimator

    mx.random.seed(0)
    net = mx.gluon.nn.Sequential()
    net.add(mx.gluon.nn.Dense(16, activation="relu"),
            mx.gluon.nn.Dense(4))
    net.initialize()
    rng = onp.random.RandomState(0)
    batches = [(mx.np.array(rng.randn(8, 8).astype("float32")),
                mx.np.array(rng.randint(0, 4, (8,)).astype("int32")))
               for _ in range(steps)]
    est = Estimator(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics="acc")
    est.fit(batches, epochs=1)
    mx.waitall()


def _workload_eager(steps: int) -> None:
    import mxnet_tpu as mx
    a = mx.nd.ones((32, 32))
    for _ in range(steps):
        b = mx.nd.dot(a, a)
        (b + 1).sum().asnumpy()
    mx.waitall()


def _workload_bulk(steps: int) -> None:
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    mx.random.seed(0)
    net = mx.gluon.nn.Sequential()
    net.add(mx.gluon.nn.Dense(32, activation="tanh"),
            mx.gluon.nn.Dense(8))
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=None)
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randn(8, 16).astype("float32"))
    y = mx.np.array(rng.randint(0, 8, (8,)).astype("int32"))
    for _ in range(max(steps, 3)):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(8)
        loss.asnumpy()
    # a host-read flush and a barrier flush for reason variety
    (x * 2 + 1).asnumpy()
    mx.waitall()


def _workload_health(steps: int) -> None:
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import faults
    from mxnet_tpu.health import HealthGuard
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {"learning_rate": 0.05},
                          mesh=make_mesh({"dp": 1},
                                         devices=jax.devices()[:1]))

    def batch_fn(step):
        rng = onp.random.RandomState(100 + step)
        return (mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4")))

    guard = HealthGuard(policy="skip", max_skips=4)
    n = max(steps, 4)
    with faults.fault_plan("trainer.step:kind=nan:times=1:after=1"):
        trainer.fit(batch_fn, n, health_guard=guard)
    mx.waitall()


def _workload_input(steps: int) -> None:
    """Async input-pipeline families: a prefetched SPMD fit whose
    loader sleeps per batch (stall + h2d + queue depth), then a seek
    (resume-style) pull to tick the invalidation counter."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.io import DevicePrefetcher
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {"learning_rate": 0.05},
                          mesh=make_mesh({"dp": 1},
                                         devices=jax.devices()[:1]))

    def batch_fn(step):
        time.sleep(0.002)
        rng = onp.random.RandomState(step)
        return (mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4")),
                mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4")))

    pf = DevicePrefetcher(batch_fn, depth=2)
    n = max(steps, 3)
    trainer.fit(pf, n)
    pf.get(0)           # non-consecutive step: invalidation ('seek')
    pf.close()
    mx.waitall()


def _workload_resilience(steps: int) -> None:
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import faults, serving
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                                   GenerationServer)

    # one-shot path: a seeded worker kill mid-batch — the request
    # requeues, the worker restarts (restart + requeue families)
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((1, 8), dtype="float32"))
    srv = serving.ModelServer(serving.load_served(net),
                              policy=serving.BucketPolicy(
                                  batch_buckets=(1, 2)),
                              timeout_ms=1.0, restart_backoff_ms=10.0)
    srv.start()
    x = onp.ones(8, "f4")
    with faults.fault_plan("serving.worker:times=1"):
        for _ in range(max(steps, 2)):
            srv.infer(x, timeout=30.0)
    srv.stop()

    # generation path: a seeded decode fault mid-stream — the sequence
    # resurrects token-identically (recovery counters + latency)
    gpt = GPTModel(vocab_size=97, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    gpt.initialize(mx.init.Normal(1.0))
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(gpt), max_slots=2,
                           kv_buckets=(16, 32, 64), max_tokens=16)
    eng.warmup()
    with GenerationServer(eng) as gs:
        with faults.fault_plan("serving.execute:after=3:times=1"):
            stream = gs.generate(onp.arange(1, 5, dtype="int32"),
                                 max_new_tokens=12)
            stream.result(timeout=60)
    mx.waitall()


def _workload_generation(steps: int) -> None:
    """Production-decoding families in one process: sampled decode
    (on-device temperature/top-k/top-p under per-slot counter keys —
    mxnet_gen_sampled_tokens_total{method}) and shared-prefix
    admissions (a common system prompt inserted cold, then hit by
    suffix-bearing and identical prompts — prefix hit/miss/eviction
    counters + the resident-rows gauge), on top of the PR-6 engine
    families (slots, TTFT, tokens/sec, prefill/decode split).  A
    second pass re-runs the mix under a truncated-layer self-
    speculative draft so the ISSUE-17 families light up too:
    mxnet_gen_spec_{proposed,accepted,rejected}_tokens_total, the
    mxnet_gen_spec_accept_rate gauge, the accepted-per-step histogram,
    and mxnet_gen_kv_rollbacks_total from rejection rollbacks."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import DecodeModel, GenerationEngine

    mx.random.seed(0)
    gpt = GPTModel(vocab_size=97, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    gpt.initialize(mx.init.Normal(1.0))
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(gpt), max_slots=4,
                           kv_buckets=(32, 64), max_tokens=16,
                           prefix_slots=2)
    eng.warmup()
    rng = onp.random.RandomState(0)
    system = rng.randint(1, 90, (16,)).astype("int32")
    streams = []
    for i in range(max(steps, 3)):
        # one shared-prefix family (first admission inserts, the rest
        # hit) + rotating sampled methods
        prompt = onp.concatenate(
            [system, rng.randint(1, 90, (1 + i % 3,)).astype("int32")])
        method = ("greedy", "sample", "top_k", "top_p")[i % 4]
        streams.append(eng.submit(
            prompt, max_new_tokens=8, method=method, seed=i,
            temperature=0.9, top_k=8, top_p=0.9))
    # a distinct-prefix flood forces LRU evictions through the bound
    for i in range(3):
        streams.append(eng.submit(
            rng.randint(1, 90, (18,)).astype("int32"),
            max_new_tokens=4))
    while not all(s.finished for s in streams):
        eng.run_iteration()

    # speculative pass: a 1-of-2-layer self-draft proposes k=3 tokens
    # per iteration; partial acceptance drives the spec counters, the
    # accept-rate gauge, and KV rollbacks — streams stay byte-identical
    # to the plain engine, so this is pure added observability
    spec = GenerationEngine(DecodeModel.from_block(gpt), max_slots=4,
                            kv_buckets=(32, 64), max_tokens=16,
                            spec_mode="self", spec_k=3,
                            spec_draft_layers=1)
    spec.warmup()
    streams = []
    for i in range(max(steps, 3)):
        method = ("greedy", "sample", "top_k", "top_p")[i % 4]
        streams.append(spec.submit(
            rng.randint(1, 90, (4 + i % 3,)).astype("int32"),
            max_new_tokens=8, method=method, seed=100 + i,
            temperature=0.9, top_k=8, top_p=0.9))
    while not all(s.finished for s in streams):
        spec.run_iteration()
    mx.waitall()


def _workload_dist_resilience(steps: int) -> None:
    """Elastic-distributed-training families in one process: a durable
    PS snapshot/restore cycle with replayed-push dedupe (generation
    bump, restore counter), heartbeat lease ages, and a coordinated
    two-phase cluster checkpoint."""
    import tempfile
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.checkpoint import CoordinatedCheckpointManager

    tmp = tempfile.mkdtemp(prefix="mxps-dist-resilience-")
    os.environ["MXNET_PS_SNAPSHOT_DIR"] = os.path.join(tmp, "snap")
    os.environ["MXNET_PS_SNAPSHOT_EVERY"] = "2"
    os.environ["MXNET_PS_HEARTBEAT_INTERVAL_S"] = "0.2"
    from mxnet_tpu.kvstore_async import PSServer, run_server, \
        KVStoreDistAsync
    import threading

    from tests.test_distributed import _free_port
    port = _free_port()
    os.environ.update(DMLC_PS_ROOT_URI="127.0.0.1",
                      DMLC_PS_ROOT_PORT=str(port),
                      DMLC_NUM_SERVER="1", DMLC_NUM_WORKER="1",
                      DMLC_WORKER_ID="0")
    ev = threading.Event()
    th = threading.Thread(target=run_server, args=(port, 1, ev),
                          daemon=True)
    th.start()
    ev.wait(20)
    kv = KVStoreDistAsync()
    kv.init("w", mx.np.zeros(8))
    for _ in range(max(steps, 3)):
        kv.push("w", mx.np.array(onp.ones(8, "f4")))
    kv.barrier()

    class _Counter:
        step = 0

        def save_checkpoint(self, prefix):
            with open(prefix + ".step", "w") as f:
                f.write(str(self.step))

        def load_checkpoint(self, prefix):
            with open(prefix + ".step") as f:
                self.step = int(f.read())

    mgr = CoordinatedCheckpointManager(os.path.join(tmp, "ckpt"), kv)
    mgr.save(_Counter(), step=max(steps, 3))
    mgr.restore(_Counter())
    # restart cycle: graceful stop (lossless snapshot) + fresh server
    # restoring it — generation bumps, the restore counter ticks, and
    # a replayed frame would dedupe
    kv.stop_servers()
    th.join(10)
    ev2 = threading.Event()
    th2 = threading.Thread(target=run_server, args=(port, 1, ev2),
                           daemon=True)
    th2.start()
    ev2.wait(20)
    kv.restart_heartbeat()
    kv.push("w", mx.np.array(onp.ones(8, "f4")))   # detects the new gen
    kv.pull("w", out=mx.np.zeros(8))
    kv.server_stats()
    kv.stop_servers()
    th2.join(10)


def _workload_dist_comm(steps: int) -> None:
    """Overlapped gradient reduction on a synthetic-slow wire: a
    16-parameter adam micro-fit through the bucketed comm-thread
    scheduler (kvstore_sched.py), showing the mxnet_kv_* families —
    buckets dispatched, per-bucket comm latency, the exposed wait,
    the per-round overlap fraction and its backward/optimizer phase
    split, buckets event-enqueued during backward
    (mxnet_kv_stream_enqueues_total, fed by per-layer backward
    segmentation — mxnet_bulk_backward_segments_total{reason}), and
    compressed-vs-raw wire bytes (the second fit runs 2bit
    error-feedback compression)."""
    import os as _os
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import ops

    _os.environ["MXNET_KV_OVERLAP"] = "1"
    _os.environ["MXNET_KV_BUCKET_BYTES"] = str(512 * 1024)
    _os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "2.0"
    _os.environ["MXNET_BULK_BACKWARD_SEGMENTS"] = "param"
    _os.environ["MXNET_KV_BACKWARD_STREAM"] = "1"
    try:
        for compression in (None, {"type": "2bit", "threshold": 1e-4}):
            mx.random.seed(0)
            ps = {}
            for j in range(16):
                p = mx.gluon.Parameter(f"w{j}", shape=(128 * 1024,))
                p.initialize()
                ps[f"w{j}"] = p
            tr = mx.gluon.Trainer(ps, "adam", {"learning_rate": 1e-3},
                                  compression_params=compression)
            for _ in range(max(steps, 2)):
                with mx.autograd.record():
                    loss = ops.add_n(
                        *[p.data()[:64] for p in ps.values()]).mean()
                loss.backward()
                tr.step(1)
                loss.asnumpy()
            mx.waitall()
    finally:
        _os.environ["MXNET_KV_SYNTH_WIRE_GBPS"] = "0"


def _workload_trace(steps: int) -> None:
    """Exemplar linkage: a fully-sampled traced generation workload —
    the serving/gen latency histograms capture the trace id of their
    slowest recent observation, surfaced as ``exemplar`` in the JSON
    exposition (``--format json``)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import tracing
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.serving import (DecodeModel, GenerationEngine,
                                   GenerationServer)

    tracing.configure(sample=1.0)
    mx.random.seed(0)
    gpt = GPTModel(vocab_size=97, num_layers=2, units=32,
                   hidden_size=48, num_heads=4, max_length=64,
                   dropout=0.0)
    gpt.initialize(mx.init.Normal(1.0))
    gpt(mx.np.zeros((1, 4), dtype="int32"))
    eng = GenerationEngine(DecodeModel.from_block(gpt), max_slots=2,
                           kv_buckets=(16, 32), max_tokens=16)
    eng.warmup()
    rng = onp.random.RandomState(0)
    with GenerationServer(eng) as gs:
        for i in range(max(steps, 2)):
            # the client-side root span is what the histograms link to
            with tracing.span("client.request", i=i):
                stream = gs.generate(
                    rng.randint(1, 90, (4 + i % 3,)).astype("int32"),
                    max_new_tokens=6)
                stream.result(timeout=60)
    mx.waitall()


WORKLOADS = {
    "resnet_step": _workload_resnet_step,
    "mlp_fit": _workload_mlp_fit,
    "eager": _workload_eager,
    "bulk": _workload_bulk,
    "health": _workload_health,
    "input": _workload_input,
    "resilience": _workload_resilience,
    "generation": _workload_generation,
    "dist-resilience": _workload_dist_resilience,
    "dist-comm": _workload_dist_comm,
    "trace": _workload_trace,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", choices=sorted(WORKLOADS),
                    default="resnet_step")
    ap.add_argument("--steps", type=int, default=3,
                    help="training steps / repeats (default 3)")
    ap.add_argument("--format", choices=("prom", "json"), default="prom")
    ap.add_argument("--platform", choices=("cpu", "ambient"),
                    default="cpu",
                    help="force the CPU backend (default) or keep the "
                         "environment's")
    args = ap.parse_args(argv)
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    WORKLOADS[args.workload](args.steps)

    from mxnet_tpu import metrics
    if args.format == "json":
        import json
        print(json.dumps(metrics.dump_json(), indent=1))
    else:
        sys.stdout.write(metrics.render_text())


if __name__ == "__main__":
    main()
