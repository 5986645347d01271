"""ModelServer / GenerationServer — batcher + model + replicated workers.

The concurrency shape mirrors the device reality: a worker drains the
queue and executes batches (a single accelerator runs one program at a
time; a second in-flight batch would only queue inside the runtime),
while any number of producer threads — the HTTP front end's
per-connection threads, in-process callers — submit requests and wait on
futures.  Backpressure is therefore explicit and bounded: the queue
limit and the deadline are the only places a request can wait.

Since ISSUE 7 the worker is no longer a single point of failure.  Both
servers host ``MXNET_SERVING_REPLICAS`` worker replicas behind a
router, and worker death is a *routine, bounded* event:

* a dead ``ModelServer`` worker's in-flight batch **requeues** to the
  surviving workers (unresolved futures only — the future is the
  exactly-once boundary for one-shot inference);
* a dead ``GenerationServer`` worker's engine is **evacuated**: queued
  requests requeue, and slot-resident sequences are **resurrected** on
  a healthy replica by re-prefilling ``prompt + tokens already
  emitted`` — greedy decode is deterministic, so the recovered stream
  is token-identical to a fault-free run, and the
  :class:`~mxnet_tpu.serving.generation.TokenStream` index dedupe makes
  the join exactly-once on the wire;
* the :class:`~mxnet_tpu.serving.replica.ReplicaSupervisor` restarts
  the dead replica with jittered backoff behind a per-replica circuit
  breaker; when every replica exhausts its budget the server degrades
  EXPLICITLY (structured :class:`DegradedError`, readiness 503,
  liveness 200) instead of crash-looping;
* SIGTERM triggers a **graceful drain**
  (:func:`serve_until_preempted`): admissions shed with 429, resident
  work finishes within ``MXNET_SERVING_DRAIN_DEADLINE_S``, readiness
  drops out of rotation first, and the process exits 0.
"""
from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError, getenv
from .. import faults as _faults
from .. import metrics as _metrics
from .. import tracing as _tracing
from .batching import (BucketPolicy, DynamicBatcher, OverloadError,
                       REQUESTS_TOTAL, Request)
from .generation import GenRequest, make_recovery_request
from .model import ServedModel
from .replica import ReplicaSupervisor

__all__ = ["ModelServer", "GenerationServer", "DegradedError",
           "serve_until_preempted"]

_LOG = logging.getLogger("mxnet_tpu.serving")


def _compile_cache_stats() -> Dict[str, Any]:
    """Where jax's persistent compilation cache is (None: off) and what
    this boot compiled and loaded from it, for /v1/model — operators
    see at a glance whether a restarted replica's warmup came from
    disk."""
    import jax
    return {"dir": jax.config.jax_compilation_cache_dir,
            "compiled": int(_metrics.COMPILE_MISSES.value),
            "loaded": int(_metrics.COMPILE_PERSISTENT_HITS.value)}


class DegradedError(MXNetError):
    """The server cannot take requests (circuit breaker open, every
    worker replica dead, or stopped) — the HTTP front end maps this to
    503, distinct from caller errors."""


class ModelServer:
    """Serve a :class:`~mxnet_tpu.serving.model.ServedModel` behind a
    dynamic micro-batching queue.

    In-process API::

        server = ModelServer(load_served("model"), warmup=True)
        server.start()
        y = server.infer(x_np)               # blocking, one sample
        fut = server.infer_async(x_np)       # concurrent.futures.Future
        server.stop()

    ``infer`` raises :class:`OverloadError` when the request is shed
    (bounded queue / deadline / draining) — callers back off; the
    server never crashes or grows its queue without bound.
    ``replicas`` worker threads (default ``MXNET_SERVING_REPLICAS``)
    drain the shared queue; a dead worker's batch requeues to the
    survivors while the supervisor restarts it.
    """

    def __init__(self, model: ServedModel,
                 policy: Optional[BucketPolicy] = None,
                 timeout_ms: Optional[float] = None,
                 queue_limit: Optional[int] = None,
                 warmup: bool = False,
                 replicas: Optional[int] = None,
                 max_restarts: Optional[int] = None,
                 restart_backoff_ms: Optional[float] = None) -> None:
        self.model = model
        self.policy = policy if policy is not None \
            else model.default_policy()
        if model.fixed_batch is not None and \
                tuple(self.policy.batch_buckets) != (model.fixed_batch,):
            raise MXNetError(
                f"static export serves only batch={model.fixed_batch}; "
                f"the policy's batch_buckets must be "
                f"[{model.fixed_batch}]")
        self.batcher = DynamicBatcher(self.policy, timeout_ms=timeout_ms,
                                      queue_limit=queue_limit)
        self._default_deadline_s = \
            float(getenv("MXNET_SERVING_DEADLINE_MS", 0)) / 1e3
        if replicas is None:
            replicas = int(getenv("MXNET_SERVING_REPLICAS", 1))
        self.replicas = max(1, int(replicas))
        self._workers: Dict[int, threading.Thread] = {}
        self._started = False
        self._stopping = False
        self._degraded = False
        # per-worker batch currently executing: a dying worker's batch
        # requeues to the survivors; stop() fails whatever remains
        self._inflight: Dict[int, List[Request]] = {}
        self._lock = threading.Lock()
        self.supervisor = ReplicaSupervisor(
            "oneshot", self.replicas, self._spawn_worker,
            self._on_degraded, self._worker_alive,
            max_restarts=max_restarts, backoff_ms=restart_backoff_ms)
        # warmup runs BEFORE start()/ready(): a prewarming server never
        # flips /healthz ready with an un-compiled bucket grid.  With
        # the persistent compile cache populated, this is a disk reload
        # (seconds), not a compile storm — warmup_seconds in /v1/model
        # is the number that proves it
        self.warmed = 0
        self.warmup_seconds = 0.0
        if warmup:
            t0 = time.perf_counter()
            self.warmed = model.warmup(self.policy)
            self.warmup_seconds = time.perf_counter() - t0

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ModelServer":
        if self._started:
            return self
        if self.batcher._closed:
            raise MXNetError(
                "ModelServer cannot restart after stop(): the batcher is "
                "closed (build a fresh ModelServer)")
        self._started = True
        for wid in range(self.replicas):
            self._spawn_worker(wid)
        return self

    def _spawn_worker(self, wid: int) -> None:
        t = threading.Thread(target=self._run, args=(wid,),
                             name=f"mxnet-serving-worker-{wid}",
                             daemon=True)
        with self._lock:
            self._workers[wid] = t
        t.start()

    def _worker_alive(self, wid: int) -> bool:
        t = self._workers.get(wid)
        return bool(t is not None and t.is_alive())

    def stop(self, timeout: float = 10.0) -> None:
        if not self._started:
            return
        self._stopping = True
        self.supervisor.stop()
        self.batcher.close()
        deadline = time.monotonic() + timeout
        for t in list(self._workers.values()):
            t.join(max(0.0, deadline - time.monotonic()))
        # strand nothing: a batch still executing when the join timed
        # out (or whose worker died) holds futures no one will ever
        # complete — fail them with a structured shutdown error so HTTP
        # clients and in-process callers unblock deterministically
        self._fail_inflight(MXNetError(
            "ModelServer stopped with the request still in flight "
            "(shutdown)"))
        self._started = False

    def _fail_inflight(self, exc: Exception) -> None:
        with self._lock:
            batches = list(self._inflight.values())
            self._inflight.clear()
        for batch in batches:
            for r in batch:
                if not r.future.done():
                    try:
                        r.future.set_exception(exc)
                    except Exception:   # noqa: BLE001 - done() race
                        continue
                    REQUESTS_TOTAL.labels(status="error").inc()

    # -- health split -------------------------------------------------------
    @property
    def draining(self) -> bool:
        return self.batcher.draining

    @property
    def degraded(self) -> bool:
        return self._degraded

    def ready(self) -> bool:
        """Readiness: in rotation for NEW traffic — started, breaker
        closed, not draining, and at least one worker serving or coming
        back.  The load balancer keys on this."""
        return bool(self._started and not self._degraded
                    and not self.draining
                    and self.supervisor.in_rotation() > 0)

    def healthy(self) -> bool:
        """Back-compat alias for :meth:`ready` (pre-replica callers)."""
        return self.ready()

    def __enter__(self) -> "ModelServer":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    # -- drain --------------------------------------------------------------
    def start_drain(self) -> None:
        """Stop admissions (new submits shed 429 ``draining``); queued
        and in-flight work keeps executing."""
        _metrics.SERVING_DRAINING.set(1)
        self.batcher.start_drain()

    def await_drained(self, timeout: float = 1.0) -> bool:
        """Poll until no request is queued or in flight (or timeout)."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                idle = not any(self._inflight.values())
            if idle and len(self.batcher) == 0:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.01)

    def drain(self, deadline_s: Optional[float] = None,
              stop_timeout: float = 10.0) -> bool:
        """Graceful shutdown: stop admissions, finish resident work
        within ``deadline_s`` (default
        ``MXNET_SERVING_DRAIN_DEADLINE_S``), then stop.  Returns True
        when everything finished inside the budget."""
        if deadline_s is None:
            deadline_s = float(
                getenv("MXNET_SERVING_DRAIN_DEADLINE_S", 30))
        self.start_drain()
        drained = self.await_drained(float(deadline_s))
        self.stop(timeout=stop_timeout)
        return drained

    # -- breaker ------------------------------------------------------------
    def _on_degraded(self, exc: BaseException) -> None:
        """Every replica exhausted its restart budget: explicit
        degraded mode — fail everything held, refuse new work."""
        self._degraded = True
        err = MXNetError(
            f"ModelServer worker thread died repeatedly "
            f"({self.supervisor.max_restarts} restarts per replica "
            f"spent); circuit breaker tripped — the server is degraded "
            f"(last error: {exc!r}); reset_breaker() or restart")
        self._fail_inflight(err)
        self.batcher.close(error=err)
        _LOG.error(
            "serving worker crash-loop: breaker tripped after %d "
            "restarts/replica — /healthz now reports degraded (503); "
            "reset_breaker() re-admits traffic (last error: %r)",
            self.supervisor.max_restarts, exc)

    def reset_breaker(self) -> None:
        """Operator acknowledgement that the crash cause is gone:
        refill every restart budget, reopen the queue, and respawn dead
        workers — traffic re-admits immediately."""
        if not self._started:
            raise MXNetError("reset_breaker() on a stopped server — "
                             "build and start a fresh one")
        self.supervisor.reset()
        self._degraded = False
        self.batcher.reopen()
        for wid in range(self.replicas):
            if not self._worker_alive(wid):
                self._spawn_worker(wid)

    # -- request API --------------------------------------------------------
    def infer_async(self, *sample: _np.ndarray,
                    deadline_ms: Optional[float] = None) -> Future:
        """Submit one sample (per-input arrays WITHOUT the batch dim);
        returns a Future of the per-output arrays (list, or the single
        array for single-output models)."""
        if not self._started:
            raise MXNetError("ModelServer.start() first")
        if self._degraded:
            # a tripped breaker would park this future forever — fail
            # the submit instead so clients back off / fail over
            raise DegradedError(
                "ModelServer worker replicas are crash-looping and the "
                "circuit breaker is open; the server is degraded "
                "(healthz reports 503) — reset_breaker() or restart it")
        arrays = [_np.asarray(a) for a in sample]
        sig = self.model.input_signature
        if len(arrays) != len(sig):
            raise MXNetError(
                f"model {self.model.name} takes {len(sig)} inputs, "
                f"got {len(arrays)}")
        for i, (a, (shape, dtype)) in enumerate(zip(arrays, sig)):
            got = tuple(a.shape)
            if i == 0 and self.policy.pad_axis is not None:
                # only the bucketed axis may vary — every other dim must
                # match, or each distinct wrong shape would become a
                # fresh bucket key (an unbounded-compile hole) or be
                # silently zero-padded into wrong answers
                ax = self.policy.pad_axis
                if len(got) != len(shape) or any(
                        g != s for j, (g, s) in enumerate(zip(got, shape))
                        if j != ax):
                    raise MXNetError(
                        f"sample shape {got} != model input {shape} "
                        f"(batch dim excluded; only axis {ax} is "
                        "length-bucketed)")
            elif got != tuple(shape):
                raise MXNetError(
                    f"sample shape {got} != model input {tuple(shape)} "
                    "(batch dim excluded); enable length bucketing "
                    "(pad_axis/length_buckets) for variable-shape "
                    "requests")
        key = self.policy.bucket_key(arrays)
        if deadline_ms is None and self._default_deadline_s > 0:
            deadline_ms = self._default_deadline_s * 1e3
        deadline_t = (time.monotonic() + deadline_ms / 1e3
                      if deadline_ms else None)
        fut: Future = Future()
        self.batcher.submit(Request(arrays, key, fut, deadline_t))
        return fut

    def infer(self, *sample: _np.ndarray,
              deadline_ms: Optional[float] = None,
              timeout: float = 60.0) -> Any:
        """Blocking single-sample inference (the in-process API)."""
        return self.infer_async(*sample,
                                deadline_ms=deadline_ms).result(timeout)

    # -- worker -------------------------------------------------------------
    def _run(self, wid: int) -> None:
        def take(batch: List[Request]) -> None:
            # runs under the batcher lock: no queued-nor-inflight gap
            # for a drain poll to mistake for idleness
            with self._lock:
                self._inflight[wid] = batch

        try:
            while True:
                batch = self.batcher.next_batch(on_take=take)
                if batch is None:
                    return
                # the worker-death chaos site: an injected error here
                # (NOT per-request handling) kills this worker thread
                _faults.maybe_fault("serving.worker", worker=wid,
                                    batch=len(batch))
                try:
                    self._execute(batch)
                except Exception:   # noqa: BLE001 - the worker must
                    # outlive any per-batch surprise (a dead worker is a
                    # wedged replica); per-request faults were
                    # already set
                    pass
                # cleared only on survival: a BaseException must leave
                # the batch visible to the death handler below
                with self._lock:
                    self._inflight.pop(wid, None)
        except BaseException as e:   # noqa: BLE001 - worker death is a
            # replica-level event: requeue its batch to the survivors
            # and let the supervisor restart it; re-raising inside a
            # worker thread would only reach threading.excepthook
            self._on_worker_death(wid, e)

    def _on_worker_death(self, wid: int, exc: BaseException) -> None:
        if self._stopping or self.batcher._closed:
            # shutdown races a death: keep the old deterministic
            # behavior — fail this worker's batch so no caller blocks
            self._fail_inflight(MXNetError(
                f"ModelServer worker thread died: {exc!r}; the server "
                "is stopping"))
            return
        with self._lock:
            batch = self._inflight.pop(wid, None)
        if batch:
            # the future is the exactly-once boundary: only unresolved
            # requests re-execute
            self.batcher.requeue(batch)
        _LOG.error(
            "serving worker %d died: %r — batch requeued to surviving "
            "replicas; supervisor restarting with backoff", wid, exc)
        self.supervisor.notify_death(wid, exc)

    def _execute(self, batch: List[Request]) -> None:
        try:
            # the execute span is its own (head-sampled) trace — a
            # batch serves many requests, so it LINKS each request's
            # trace id instead of parenting under any one of them
            with _tracing.span("serving.execute",
                               batch=len(batch)) as xsp:
                for _r in batch:
                    _tr = getattr(_r, "trace", None)
                    if _tr is not None:
                        xsp.add_link(_tr.trace_id)
                _faults.maybe_fault("serving.execute", batch=len(batch))
                arrays, _nb = self.policy.assemble(
                    [r.sample for r in batch], batch[0].key)
                # per-batch execute deadline: the training hang
                # watchdog reused for serving
                # (MXNET_HEALTH_STEP_DEADLINE_S) — a wedged model
                # execute dumps all-thread stacks instead of silently
                # eating the queue's deadline budget
                from .. import health as _health
                with _health.watch_section("serving.execute",
                                           batch=len(batch)):
                    outs = self.model.predict(arrays)
        except Exception as e:   # noqa: BLE001 - worker must survive
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)
                    REQUESTS_TOTAL.labels(status="error").inc()
            return
        for i, r in enumerate(batch):
            if r.future.done():
                # cancelled (or shed) while queued/executing: a result
                # set now would raise InvalidStateError
                continue
            rows = [o[i] for o in outs]
            if self.policy.pad_axis is not None:
                # slice length padding back off axis pad_axis of each
                # output that still carries the padded extent
                rows = self._strip_length(rows, r)
            try:
                r.future.set_result(rows[0] if len(rows) == 1 else rows)
            except Exception:   # noqa: BLE001 - cancelled in the
                continue        # done()->here window; keep distributing
            REQUESTS_TOTAL.labels(status="ok").inc()

    def _strip_length(self, rows: List[_np.ndarray],
                      req: Request) -> List[_np.ndarray]:
        """Heuristic by necessity: outputs carry no axis metadata, so an
        output is taken to keep the length axis when it has at least the
        sample's rank AND the padded extent at pad_axis.  Requiring the
        full rank keeps reduced outputs (a pooled logits vector whose
        size merely equals a bucket length) untouched."""
        real = req.sample[0].shape[self.policy.pad_axis]
        padded = req.key[0][0][self.policy.pad_axis]
        if real == padded:
            return rows
        sample_ndim = req.sample[0].ndim
        out = []
        for o in rows:
            ax = self.policy.pad_axis
            if o.ndim >= sample_ndim and o.ndim > ax \
                    and o.shape[ax] == padded:
                sl = [slice(None)] * o.ndim
                sl[ax] = slice(0, real)
                o = o[tuple(sl)]
            out.append(o)
        return out

    # -- introspection ------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        from ..ndarray.register import exec_cache_stats
        return {
            "model": self.model.describe(),
            "policy": {
                "batch_buckets": list(self.policy.batch_buckets),
                "pad_axis": self.policy.pad_axis,
                "length_buckets": (list(self.policy.length_buckets)
                                   if self.policy.length_buckets else None),
                "n_buckets": self.policy.n_buckets(),
            },
            "queue": {"depth": len(self.batcher),
                      "limit": self.batcher.queue_limit,
                      "batch_timeout_ms": self.batcher.timeout_s * 1e3},
            "warmed_buckets": self.warmed,
            "warmup_seconds": round(self.warmup_seconds, 6),
            "compile_cache": _compile_cache_stats(),
            "worker_alive": self.ready(),
            "resilience": {
                "replicas": self.replicas,
                "workers_alive": sum(
                    1 for wid in range(self.replicas)
                    if self._worker_alive(wid)),
                "draining": self.draining,
                "supervisor": self.supervisor.describe(),
            },
            "exec_cache": exec_cache_stats(),
        }


class _GenReplica:
    """One generation worker replica: its engine, its thread.
    ``dead`` flips the moment the death handler starts so the router
    stops feeding an engine that is being evacuated."""

    __slots__ = ("idx", "engine", "thread", "dead")

    def __init__(self, idx: int, engine: Any) -> None:
        self.idx = idx
        self.engine = engine
        self.thread: Optional[threading.Thread] = None
        self.dead = False


class GenerationServer:
    """Host :class:`~mxnet_tpu.serving.generation.GenerationEngine`
    replicas on worker threads — the continuous-batching sibling of
    :class:`ModelServer`.

    The same concurrency shape per replica: ONE worker owns its engine
    (it runs the resident decode loop, one iteration at a time, each
    iteration watchdog-armed inside the engine), while any number of
    producer threads submit prompts and drain their
    :class:`~mxnet_tpu.serving.generation.TokenStream`.  A router picks
    the least-loaded healthy replica per request; worker death
    evacuates the replica's engine and resurrects its sequences on the
    survivors (exactly-once, token-identical — see the module doc).

    ::

        server = GenerationServer(engine, warmup=True).start()
        stream = server.generate(prompt_ids, max_new_tokens=64)
        for tok in stream: ...
        server.stop()

    Pass ``engine_factory=`` (and optionally ``replicas=``, default
    ``MXNET_SERVING_REPLICAS``) to host N independent engines; dead
    replicas are then rebuilt from the factory on restart.  Passing a
    single ``engine`` keeps the pre-replica behavior (one replica,
    restart reuses the evacuated engine).
    """

    def __init__(self, engine: Any = None, warmup: bool = False,
                 engine_factory: Optional[Callable[[], Any]] = None,
                 replicas: Optional[int] = None,
                 max_restarts: Optional[int] = None,
                 restart_backoff_ms: Optional[float] = None) -> None:
        if (engine is None) == (engine_factory is None):
            raise MXNetError(
                "GenerationServer takes an engine OR an engine_factory")
        self._factory = engine_factory
        self._warmup = bool(warmup)
        if engine is not None:
            engines = [engine]
        else:
            if replicas is None:
                replicas = int(getenv("MXNET_SERVING_REPLICAS", 1))
            engines = [engine_factory() for _ in range(max(1,
                                                           int(replicas)))]
        self.replicas = len(engines)
        self._replicas = [
            _GenReplica(i, eng) for i, eng in enumerate(engines)]
        self._started = False
        self._degraded = False
        self._draining = False
        self._stop = threading.Event()
        self._lock = threading.Lock()
        # accepted requests waiting for a replica to come back (every
        # replica dead/restarting): flushed on restart, failed on
        # degrade/stop — never silently dropped
        self._pending: List[GenRequest] = []
        self.supervisor = ReplicaSupervisor(
            "generation", self.replicas, self._spawn_replica,
            self._on_degraded, self._replica_alive,
            max_restarts=max_restarts, backoff_ms=restart_backoff_ms)
        # prewarm BEFORE any replica thread exists or ready() can flip:
        # a restarted replica re-populates its whole program grid from
        # the persistent compile cache here, and /v1/model reports how
        # long that took (warmup_seconds)
        self.warmup_seconds = 0.0
        t0 = time.perf_counter()
        for rep in self._replicas:
            rep.engine.recovery_sink = self._recover
            if warmup:
                rep.engine.warmup()
        if warmup:
            self.warmup_seconds = time.perf_counter() - t0

    # -- compat surface ------------------------------------------------------
    @property
    def engine(self) -> Any:
        """The first replica's engine (pre-replica API compat)."""
        return self._replicas[0].engine

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "GenerationServer":
        if self._started:
            return self
        if self.engine.scheduler.closed:
            raise MXNetError(
                "GenerationServer cannot restart after stop(): build a "
                "fresh engine")
        self._started = True
        for rep in self._replicas:
            self._spawn_thread(rep)
        return self

    def _spawn_thread(self, rep: _GenReplica) -> None:
        t = threading.Thread(
            target=self._run, args=(rep,),
            name=f"mxnet-generation-worker-{rep.idx}", daemon=True)
        rep.thread = t
        t.start()

    def _replica_alive(self, rid: int) -> bool:
        rep = self._replicas[rid]
        # the death handler runs ON the dying thread, so is_alive() is
        # still True mid-evacuation — the dead flag closes that window
        return bool(not rep.dead and rep.thread is not None
                    and rep.thread.is_alive())

    def stop(self, timeout: float = 10.0) -> None:
        if not self._started:
            return
        self._stop.set()
        self.supervisor.stop()
        # close the admission queues: sheds queued requests with a
        # structured shutdown error and wakes parked workers
        for rep in self._replicas:
            rep.engine.scheduler.close()
        deadline = time.monotonic() + timeout
        for rep in self._replicas:
            if rep.thread is not None:
                rep.thread.join(max(0.0, deadline - time.monotonic()))
        # whether the workers exited cleanly or not, no stream may be
        # left to block forever
        for rep in self._replicas:
            rep.engine.close()
        err = MXNetError("generation server stopped with the request "
                         "still pending (shutdown)")
        with self._lock:
            pending, self._pending = self._pending, []
        for req in pending:
            req.fail(err)
        self._started = False

    # -- health split -------------------------------------------------------
    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def degraded(self) -> bool:
        return self._degraded

    def ready(self) -> bool:
        """Readiness: in rotation for NEW prompts — started, breaker
        closed, not draining, at least one replica serving or coming
        back."""
        return bool(self._started and not self._degraded
                    and not self._draining
                    and self.supervisor.in_rotation() > 0)

    def healthy(self) -> bool:
        """Back-compat alias for :meth:`ready`."""
        return self.ready()

    def __enter__(self) -> "GenerationServer":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    # -- drain --------------------------------------------------------------
    def start_drain(self) -> None:
        """Stop admitting NEW prompts (429 ``draining``); queued and
        slot-resident sequences decode to completion."""
        _metrics.SERVING_DRAINING.set(1)
        self._draining = True

    def await_drained(self, timeout: float = 1.0) -> bool:
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                pending = bool(self._pending)
            idle = not pending and not any(
                rep.engine.scheduler.busy() for rep in self._replicas)
            if idle:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.01)

    def drain(self, deadline_s: Optional[float] = None,
              stop_timeout: float = 10.0) -> bool:
        """Stop admissions, finish every accepted sequence within
        ``deadline_s`` (default ``MXNET_SERVING_DRAIN_DEADLINE_S``),
        then stop.  Returns True when everything finished in budget
        (leftovers fail with the structured shutdown error)."""
        if deadline_s is None:
            deadline_s = float(
                getenv("MXNET_SERVING_DRAIN_DEADLINE_S", 30))
        self.start_drain()
        drained = self.await_drained(float(deadline_s))
        self.stop(timeout=stop_timeout)
        return drained

    # -- request API --------------------------------------------------------
    def generate(self, tokens: Any, max_new_tokens: int = 64,
                 eos_token: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 method: Optional[str] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None,
                 speculative: Optional[bool] = None) -> Any:
        """Submit one prompt; returns its ``TokenStream``.  Sampling
        parameters pass through to the engine (on-device sampling,
        deterministic by ``seed`` — including across worker-death
        resurrection), as does ``speculative`` (None = the engine's
        MXNET_GEN_SPEC_MODE default; the flag rides recovery, so a
        resurrected sequence keeps its draft config and its bytes).
        Sheds with ``OverloadError`` (queue full / no
        slot within deadline / draining / every replica mid-restart)
        and refuses with :class:`DegradedError` when the breaker is
        open — the same 429-vs-503 split as the one-shot path."""
        if not self._started:
            raise MXNetError("GenerationServer.start() first")
        if self._degraded:
            raise DegradedError(
                "generation worker replicas are crash-looping and the "
                "circuit breaker is open; the server is degraded "
                "(healthz reports 503) — reset_breaker() or restart it")
        if self._draining:
            from .batching import SHED_TOTAL
            SHED_TOTAL.labels(reason="draining").inc()
            REQUESTS_TOTAL.labels(status="shed").inc()
            raise OverloadError("draining", retry_after_ms=1e3)
        reps = sorted(
            (rep for rep in self._replicas
             if self._replica_alive(rep.idx)
             and not rep.engine.scheduler.closed),
            key=lambda rep: (len(rep.engine.scheduler)
                             + rep.engine.scheduler.n_active()))
        if not reps:
            if self.supervisor.any_pending():
                # transient: every replica is mid-restart — structured
                # backpressure, not a fake acceptance that could die
                raise OverloadError(
                    "restarting",
                    retry_after_ms=self.supervisor.backoff_ms)
            raise DegradedError(
                "no generation worker replica is alive; the server is "
                "degraded (healthz reports 503) — restart it")
        last: Optional[OverloadError] = None
        for rep in reps:
            try:
                return rep.engine.submit(
                    tokens, max_new_tokens=max_new_tokens,
                    eos_token=eos_token, deadline_ms=deadline_ms,
                    method=method, temperature=temperature,
                    top_k=top_k, top_p=top_p, seed=seed,
                    speculative=speculative)
            except OverloadError as e:
                last = e                 # replica full: try the next
        raise last if last is not None else MXNetError(
            "no replica accepted the request")

    # -- worker -------------------------------------------------------------
    def _run(self, rep: _GenReplica) -> None:
        try:
            while not self._stop.is_set():
                if not rep.engine.scheduler.wait_for_work(0.5):
                    return               # closed and fully drained
                if len(rep.engine.scheduler) \
                        or rep.engine.scheduler.n_active():
                    # the worker-death chaos site, hit only on passes
                    # with work so seeded after=N plans count decode
                    # activity, not idle parks
                    _faults.maybe_fault("serving.worker",
                                        replica=rep.idx)
                rep.engine.run_iteration()
        except BaseException as e:   # noqa: BLE001 - worker death is a
            # replica-level event: evacuate + resurrect elsewhere
            self._on_worker_death(rep, e)

    def _on_worker_death(self, rep: _GenReplica, exc: BaseException) -> None:
        if self._stop.is_set():
            try:
                rep.engine.close()
            except Exception:   # noqa: BLE001 - already dying
                pass
            return
        _LOG.error(
            "generation worker %d died: %r — evacuating its sequences "
            "to surviving replicas; supervisor restarting with backoff",
            rep.idx, exc)
        rep.dead = True          # router must not feed a dying engine
        try:
            queued, resident = rep.engine.evacuate()
        except Exception:   # noqa: BLE001 - the engine is too broken
            # even to evacuate: strand nothing — close() fails every
            # stream it still holds so waiters unblock deterministically
            queued, resident = [], []
            try:
                rep.engine.close()
            except Exception:   # noqa: BLE001 - already beyond help
                pass
        for req in queued:
            _metrics.SERVING_RECOVERIES_TOTAL.labels(site="queue").inc()
            self._route(req, exclude=rep)
        self._recover(resident, exc, "worker", exclude=rep)
        self.supervisor.notify_death(rep.idx, exc)

    def _recover(self, victims: Sequence[GenRequest],
                 exc: BaseException, site: str,
                 exclude: Optional[_GenReplica] = None) -> None:
        """Resurrect slot-resident sequences from their stream
        transcripts (exactly-once: deterministic greedy re-prefill +
        the TokenStream index dedupe).  Each sequence carries a
        recovery budget (the supervisor's restart budget, reused): a
        deterministically-poisoned sequence that crashes every decode
        step it joins must eventually FAIL with the underlying error,
        not resurrect forever while churning its slot-mates."""
        for req in victims:
            if req.recoveries >= self.supervisor.max_restarts:
                req.fail(MXNetError(
                    f"sequence recovered {req.recoveries} times and "
                    f"failed again ({exc!r}); recovery budget spent — "
                    "failing it instead of resurrecting forever"))
                REQUESTS_TOTAL.labels(status="error").inc()
                continue
            try:
                # the resurrection stays inside the original request's
                # trace: attach its captured context so the recovery
                # span (and the re-prefill that follows on the new
                # replica) share the request's trace id
                with _tracing.attach(req.trace), _tracing.child_span(
                        "serving.recover", site=site,
                        request_id=req.request_id,
                        recovered_tokens=len(req.stream.tokens)):
                    r = make_recovery_request(req)
            except MXNetError as e:
                req.fail(e)
                REQUESTS_TOTAL.labels(status="error").inc()
                continue
            _metrics.SERVING_RECOVERIES_TOTAL.labels(site=site).inc()
            _metrics.SERVING_RECOVERED_TOKENS.inc(len(req.stream.tokens))
            self._route(r, exclude=exclude)

    def _route(self, req: GenRequest,
               exclude: Optional[_GenReplica] = None) -> None:
        """Hand an already-accepted request to a healthy replica, or
        park it for the next restart — never shed, never drop."""
        reps = sorted(
            (rep for rep in self._replicas
             if rep is not exclude and self._replica_alive(rep.idx)
             and not rep.engine.scheduler.closed),
            key=lambda rep: (len(rep.engine.scheduler)
                             + rep.engine.scheduler.n_active()))
        for rep in reps:
            try:
                rep.engine.submit_request(req, front=True)
                return
            except MXNetError:
                continue                 # closed in a race: next
        with self._lock:
            if not self._degraded and not self._stop.is_set():
                self._pending.append(req)
                return
        req.fail(DegradedError(
            "sequence lost its worker and no replica is available "
            "(server degraded/stopping)"))
        REQUESTS_TOTAL.labels(status="error").inc()

    def _spawn_replica(self, rid: int) -> None:
        """Supervisor callback (after backoff): bring replica ``rid``
        back — fresh engine from the factory when there is one, the
        evacuated engine otherwise — and flush parked requests into
        it."""
        if self._stop.is_set() or self._degraded:
            return
        rep = self._replicas[rid]
        late_q: List[GenRequest] = []
        late_r: List[GenRequest] = []
        if self._factory is not None:
            eng = self._factory()
            if self._warmup:
                eng.warmup()
            # a generate() that read the replica as alive just before
            # the dead flag flipped may have queued into the old engine
            # AFTER its evacuation — sweep once more before orphaning it
            try:
                late_q, late_r = rep.engine.evacuate()
            except Exception:   # noqa: BLE001 - poisoned old engine
                pass
        else:
            eng = rep.engine             # evacuated + buffers reset
        eng.recovery_sink = self._recover
        with self._lock:
            if self._stop.is_set() or self._degraded:
                return
            rep.engine = eng
            rep.dead = False
            pending, self._pending = self._pending, []
        self._spawn_thread(rep)
        for req in late_q + pending:
            try:
                rep.engine.submit_request(req, front=True)
            except MXNetError as e:
                req.fail(e)
                REQUESTS_TOTAL.labels(status="error").inc()
        if late_r:
            self._recover(late_r, MXNetError(
                "worker died while the sequence was being admitted"),
                "worker")

    # -- breaker ------------------------------------------------------------
    def _on_degraded(self, exc: BaseException) -> None:
        self._degraded = True
        err = DegradedError(
            f"generation worker replicas died repeatedly "
            f"({self.supervisor.max_restarts} restarts per replica "
            f"spent); circuit breaker tripped — the server is degraded "
            f"(last error: {exc!r}); reset_breaker() or restart")
        with self._lock:
            pending, self._pending = self._pending, []
        for req in pending:
            req.fail(err)
            REQUESTS_TOTAL.labels(status="error").inc()
        for rep in self._replicas:
            try:
                queued, resident = rep.engine.evacuate()
            except Exception:   # noqa: BLE001 - poisoned engine
                continue
            for req in queued + resident:
                req.fail(err)
                REQUESTS_TOTAL.labels(status="error").inc()
        _LOG.error(
            "generation worker crash-loop: breaker tripped after %d "
            "restarts/replica — /healthz now reports degraded (503); "
            "reset_breaker() re-admits traffic (last error: %r)",
            self.supervisor.max_restarts, exc)

    def reset_breaker(self) -> None:
        """Refill every restart budget and bring dead replicas back —
        the operator's re-admit-traffic lever."""
        if not self._started:
            raise MXNetError("reset_breaker() on a stopped server — "
                             "build and start a fresh one")
        self.supervisor.reset()
        self._degraded = False
        for rep in self._replicas:
            if not self._replica_alive(rep.idx):
                self._spawn_replica(rep.idx)

    # -- introspection ------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        d = self.engine.describe()
        if self.replicas > 1:
            d["slots"] = {
                "max": sum(rep.engine.max_slots
                           for rep in self._replicas),
                "active": sum(rep.engine.scheduler.n_active()
                              for rep in self._replicas),
                "free": sum(len(rep.engine.cache.free_slots())
                            for rep in self._replicas),
            }
            d["queue"] = {
                "depth": sum(len(rep.engine.scheduler)
                             for rep in self._replicas),
                "limit": sum(rep.engine.scheduler.queue_limit
                             for rep in self._replicas),
            }
        d["worker_alive"] = self.ready()
        d["warmup_seconds"] = round(self.warmup_seconds, 6)
        d["compile_cache"] = _compile_cache_stats()
        d["resilience"] = {
            "replicas": self.replicas,
            "workers_alive": sum(
                1 for rep in self._replicas
                if self._replica_alive(rep.idx)),
            "draining": self._draining,
            "pending_recoveries": len(self._pending),
            "supervisor": self.supervisor.describe(),
        }
        return d


def serve_until_preempted(httpd: Any, *servers: Any,
                          deadline_s: Optional[float] = None,
                          poll_s: float = 0.2) -> bool:
    """Run the HTTP front end until SIGTERM/SIGINT, then drain
    gracefully — the zero-downtime rolling-restart contract:

    1. the first signal (via :class:`~mxnet_tpu.preemption.
       PreemptionGuard`) stops admissions: readiness flips 503 so the
       balancer routes away, new requests shed 429 ``draining`` —
       never a connection reset;
    2. resident sequences/batches finish within ``deadline_s``
       (default ``MXNET_SERVING_DRAIN_DEADLINE_S``) while liveness
       stays 200;
    3. the HTTP listener closes, the servers stop, and the caller
       exits 0 (a second signal escalates through the guard — a wedged
       drain is still killable).

    Returns True when every accepted request finished inside the
    budget (leftovers failed with structured shutdown errors).
    """
    from ..preemption import PreemptionGuard

    if deadline_s is None:
        deadline_s = float(getenv("MXNET_SERVING_DRAIN_DEADLINE_S", 30))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    drained = True
    with PreemptionGuard() as guard:
        while not guard.wait(poll_s):
            pass
        _LOG.warning(
            "%s received: draining — admissions shed (429), readiness "
            "503, finishing resident work within %.0fs",
            guard.signal_name or "signal", deadline_s)
        for s in servers:
            s.start_drain()
        deadline = time.monotonic() + float(deadline_s)
        drained = False
        while time.monotonic() < deadline:
            if all(s.await_drained(0.2) for s in servers):
                drained = True
                break
        httpd.shutdown()
        for s in servers:
            s.stop()
    _LOG.warning("drain %s; exiting",
                 "complete" if drained else
                 "deadline exceeded (leftovers failed structurally)")
    return drained
