"""Asynchronous device-prefetch input pipeline — overlap host input
work with device compute.

The MXNet paper's dependency engine exists to hide host latency behind
asynchronous device execution (arXiv:1512.01274 §4); the Julia→TPU
full-compilation work makes the same point from the other side: the hot
loop must stay free of host↔device round-trips.  On this stack the
compiled step already runs asynchronously — what serialized the loop
was the INPUT side: every batch paid host preprocessing plus a
synchronous ``device_put`` between two steps, so the device idled for
exactly that long each step (the single-chip resnet50 plateau,
BENCH_r02–r05).

:class:`DevicePrefetcher` moves that work onto a background thread: while
step N executes, the thread fetches batch N+1, runs host preprocessing,
and commits it to the device (sharded ``jax.device_put`` when attached
to an :class:`~mxnet_tpu.parallel.SPMDTrainer`), queueing up to
``MXNET_PREFETCH_DEPTH`` batches ahead.  The step loop's only per-step
input work is a queue pop of an already-device-resident batch.

Two modes, one class:

* **callable mode** — wrap a ``batch_fn(step[, salt])``; the consumer
  pulls with :meth:`get`.  ``SPMDTrainer.fit`` detects the wrapper and
  drives it directly, composing with checkpoint resume and HealthGuard
  rewind: a non-consecutive ``step`` or a changed ``salt`` invalidates
  every prefetched batch and reseeks the producer (counted in
  ``mxnet_prefetch_invalidated_total``).
* **iterable mode** — wrap a ``DataLoader`` / ``DataIter`` / any
  iterable of ``(data, label)`` batches; each ``iter()`` starts a fresh
  epoch producer.  Drop-in for ``Estimator.fit(train_data=...)`` and
  hand-written gluon loops.

Failure semantics: the ``dataloader.worker`` fault site fires inside
the prefetch thread (per batch), and any producer error — injected or
real — surfaces as a structured :class:`~mxnet_tpu.base.MXNetError` on
the consumer's next pull, never a hang.  A *wedged* producer is a named
stall: the blocking pull is armed on the PR-5 hang watchdog as site
``prefetch.get`` (``MXNET_HEALTH_STEP_DEADLINE_S``), so a stuck loader
dumps all-thread stacks instead of silently stalling the job.

Instrumentation (the overlap is provable, not vibes):
``mxnet_prefetch_queue_depth``, ``mxnet_prefetch_h2d_seconds``,
``mxnet_prefetch_stall_seconds`` (time the step loop waited on input),
``mxnet_prefetch_batches_total``, ``mxnet_prefetch_invalidated_total``.
"""
from __future__ import annotations

import inspect
import queue as _queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

from ..base import MXNetError, getenv, register_env
from .. import metrics as _metrics

__all__ = ["DevicePrefetcher", "default_placement", "takes_salt"]

register_env(
    "MXNET_PREFETCH_DEPTH", 2,
    "Queue depth of the DevicePrefetcher (io/prefetch.py): how many "
    "batches the background thread fetches, preprocesses, and commits "
    "to the device ahead of the training step. 2 (default) double-"
    "buffers: batch N+1 lands while step N executes. Deeper only helps "
    "loaders with high per-batch jitter; every queued batch holds "
    "device memory.")
register_env(
    "MXNET_PREFETCH_DONATE", 1,
    "When 1 (default), SPMDTrainer.fit donates prefetched batch "
    "buffers to the compiled step (XLA reuses the input memory for "
    "outputs). Safe because the prefetcher hands every step a fresh "
    "batch; set 0 if a custom loop re-reads batch arrays after the "
    "step (a donated buffer is deleted by the call). Only applies to "
    "prefetched fit() loops — manual step() calls never donate "
    "inputs.")


def takes_salt(fn: Any) -> bool:
    """Whether ``fn(step, salt=...)`` is accepted — the HealthGuard
    rewind-replay perturbation contract, shared by the prefetched and
    bare-callable ``SPMDTrainer.fit`` paths (``**kwargs``-only
    signatures read as salt-less: the salt must be a named, consumed
    parameter to perturb anything)."""
    try:
        return "salt" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def default_placement(batch: Any) -> Any:
    """Commit every array in ``batch`` (nested tuples/lists of NDArray /
    numpy / jax arrays) to the default device with ``jax.device_put``.

    Committed placement matters beyond the transfer itself: jit caches
    key on committed-ness, so an uncommitted batch can force the slow
    uncommitted-argument dispatch path on every consuming call (the
    PR-6 KV-cache lesson).  Consumers with sharding requirements
    (SPMDTrainer) install their own placement via
    :meth:`DevicePrefetcher.attach`."""
    import jax
    from ..ndarray.ndarray import NDArray, from_jax
    dev = jax.devices()[0]

    def place(x: Any) -> Any:
        if isinstance(x, (tuple, list)):
            return type(x)(place(v) for v in x)
        if isinstance(x, NDArray):
            x._data = jax.device_put(x._data, dev)
            return x
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return from_jax(jax.device_put(x, dev))
        return x

    return place(batch)


def _raise_producer_error(exc: BaseException) -> None:
    """Surface a prefetch-thread failure as a structured error on the
    consumer thread (FaultInjected and other MXNetErrors pass through
    typed — the blast radius is the consuming run, exactly as a
    DataLoader worker error)."""
    if isinstance(exc, MXNetError):
        raise exc
    if isinstance(exc, StopIteration):
        raise exc
    raise MXNetError(
        f"prefetch worker failed: {type(exc).__name__}: {exc} "
        "[mxnet_tpu.io.prefetch]") from exc


class _EpochIterator:
    """One epoch's background producer over ``iter(source)`` (iterable
    mode): fetch + place on the thread, stall-timed pops on the
    consumer."""

    def __init__(self, pf: "DevicePrefetcher") -> None:
        self._pf = pf
        self._q: "_queue.Queue" = _queue.Queue(maxsize=pf.depth)
        self._closed = False
        self._dead: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="mxnet-prefetch-epoch", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        from .. import faults as _faults
        try:
            it = iter(self._pf._source)
        except BaseException as exc:   # noqa: BLE001 - relay to consumer
            self._dead = exc
            self._put((None, exc))
            return
        while not self._closed:
            try:
                if _faults._ARMED:
                    _faults.maybe_fault("dataloader.worker",
                                        thread="prefetch")
                batch = next(it)
                t0 = time.perf_counter()
                batch = self._pf._placement(batch)
                _metrics.PREFETCH_H2D_SECONDS.observe(
                    time.perf_counter() - t0)
            except StopIteration:
                self._put((None, None))          # clean end of epoch
                return
            except BaseException as exc:   # noqa: BLE001 - relay
                self._dead = exc
                self._put((None, exc))
                return
            self._put((batch, None))
            _metrics.PREFETCH_BATCHES_TOTAL.inc()
            _metrics.PREFETCH_QUEUE_DEPTH.set(self._q.qsize())

    def _put(self, item: Any) -> None:
        while not self._closed:
            try:
                self._q.put(item, timeout=0.1)
                return
            except _queue.Full:
                continue

    def __iter__(self) -> "_EpochIterator":
        return self

    def __next__(self) -> Any:
        from .. import health as _health
        t0 = time.perf_counter()
        with _health.watch_section("prefetch.get"):
            while True:
                if self._dead is not None and self._q.empty():
                    _raise_producer_error(self._dead)
                if self._closed and self._q.empty():
                    # exhausted (or externally closed) epoch: the
                    # producer is gone, nothing more can arrive
                    raise StopIteration
                try:
                    batch, exc = self._q.get(timeout=0.2)
                    break
                except _queue.Empty:
                    continue
        _metrics.PREFETCH_STALL_SECONDS.observe(time.perf_counter() - t0)
        _metrics.PREFETCH_QUEUE_DEPTH.set(self._q.qsize())
        if exc is not None:
            _raise_producer_error(exc)
        if batch is None:
            self.close()
            raise StopIteration
        return batch

    def close(self) -> None:
        self._closed = True
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                break
        # wait the producer out before the caller tears down the
        # underlying source (a RecordIO loader closed under an
        # in-flight next() is a native use-after-close); a producer
        # wedged inside the source itself is bounded by the timeout
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=2.0)

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:   # noqa: BLE001 - interpreter teardown
            pass


class DevicePrefetcher:
    """Background host-fetch + device-commit of batch N+1 while step N
    executes (module docstring has the full story).

    Parameters
    ----------
    source : callable ``step[, salt] -> (data, labels)`` or an iterable
        of batches.  Callable mode supports :meth:`get` with seek/salt
        invalidation (checkpoint resume, HealthGuard rewind replay);
        iterable mode supports ``iter()`` per epoch.
    depth : queue depth (default ``MXNET_PREFETCH_DEPTH``).
    placement : ``batch -> batch`` moving arrays to the device; default
        commits to the default device.  ``SPMDTrainer.fit`` installs
        its sharded placement via :meth:`attach`.
    donate : whether a prefetched ``fit`` loop may donate batch buffers
        to the compiled step (default ``MXNET_PREFETCH_DONATE``).
    start_step : first step the callable producer fetches (resume can
        also just call ``get(restored_step)`` — the seek is automatic).
    """

    def __init__(self, source: Any, depth: Optional[int] = None,
                 placement: Optional[Callable[[Any], Any]] = None,
                 donate: Optional[bool] = None,
                 start_step: int = 0) -> None:
        self._source = source
        self.is_callable = callable(source)
        self.depth = int(depth if depth is not None
                         else getenv("MXNET_PREFETCH_DEPTH", 2))
        if self.depth < 1:
            raise MXNetError(
                f"prefetch depth must be >= 1, got {self.depth} "
                "(MXNET_PREFETCH_DEPTH)")
        self.donate = (bool(int(getenv("MXNET_PREFETCH_DONATE", 1)))
                       if donate is None else bool(donate))
        self._placement = placement or default_placement
        self.takes_salt = self.is_callable and takes_salt(source)
        # callable-mode producer state (guarded by _lock; the consumer
        # side of _expect/_salt is single-threaded by contract)
        self._lock = threading.Lock()
        self._gen = 0
        self._closed = False
        self._dead: Optional[BaseException] = None
        self._next_step = int(start_step)
        self._salt = 0
        self._expect = int(start_step)
        self._q: "_queue.Queue" = _queue.Queue(maxsize=self.depth)
        self._thread: Optional[threading.Thread] = None

    # -- wiring --------------------------------------------------------------
    def attach(self, trainer: Any) -> "DevicePrefetcher":
        """Bind this prefetcher's placement to a trainer's input
        shardings (``SPMDTrainer.fit`` calls this): batches then arrive
        at the step already committed to their mesh shardings, and
        ``step()``'s own placement short-circuits to a no-op."""
        placer = getattr(trainer, "input_placement", None)
        if placer is not None:
            self._placement = placer()
        return self

    def _ensure_started(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            if self._dead is not None or self._closed:
                return
            self._thread = threading.Thread(
                target=self._run, name="mxnet-prefetch", daemon=True)
            self._thread.start()

    # -- callable-mode producer ----------------------------------------------
    def _run(self) -> None:
        from .. import faults as _faults
        while True:
            with self._lock:
                if self._closed:
                    return
                gen, step, salt = self._gen, self._next_step, self._salt
                self._next_step += 1
            try:
                if _faults._ARMED:
                    _faults.maybe_fault("dataloader.worker", step=step,
                                        thread="prefetch")
                batch = (self._source(step, salt=salt) if self.takes_salt
                         else self._source(step))
                t0 = time.perf_counter()
                batch = self._placement(batch)
                _metrics.PREFETCH_H2D_SECONDS.observe(
                    time.perf_counter() - t0)
            except BaseException as exc:   # noqa: BLE001 - relay
                # the producer dies with the error (DataLoader worker
                # blast radius); _dead wakes a consumer even if the
                # queue item itself is dropped as stale
                self._dead = exc
                self._force_put((gen, step, None, exc))
                return
            if not self._put((gen, step, batch, None)):
                continue            # seek happened mid-fetch: dropped
            _metrics.PREFETCH_BATCHES_TOTAL.inc()
            _metrics.PREFETCH_QUEUE_DEPTH.set(self._q.qsize())

    def _put(self, item: Any) -> bool:
        """Queue ``item`` unless it became stale (gen changed) or the
        pipeline closed; returns whether it was queued."""
        while True:
            with self._lock:
                if self._closed:
                    return False
                if item[0] != self._gen:
                    return False
            try:
                self._q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue

    def _force_put(self, item: Any) -> None:
        """Best-effort wake-up put for terminal errors (staleness does
        not matter: ``_dead`` is authoritative)."""
        try:
            self._q.put_nowait(item)
        except _queue.Full:
            pass

    # -- callable-mode consumer ----------------------------------------------
    def get(self, step: int, salt: int = 0) -> Any:
        """Device-resident batch for ``step`` — the callable-mode pull.

        Consecutive steps stream straight off the queue; a
        non-consecutive ``step`` (resume, rewind) or a changed ``salt``
        (HealthGuard replay perturbation) invalidates every prefetched
        batch and reseeks the producer.  Blocks until the batch is
        ready (the wait is the ``mxnet_prefetch_stall_seconds``
        observation and is watchdog-armed as ``prefetch.get``)."""
        if not self.is_callable:
            raise MXNetError(
                "DevicePrefetcher.get(step) needs a callable batch_fn "
                "source; iterable sources are consumed with iter()")
        if self._closed:
            raise MXNetError(
                "DevicePrefetcher is closed; create a new prefetcher "
                "to keep training [mxnet_tpu.io.prefetch]")
        if self._dead is not None:
            _raise_producer_error(self._dead)
        if step != self._expect or salt != self._salt:
            self._seek(step, salt)
        self._ensure_started()
        from .. import health as _health
        from .. import tracing as _tracing
        t0 = time.perf_counter()
        with _tracing.child_span("prefetch.get", step=step), \
                _health.watch_section("prefetch.get", step=step):
            while True:
                if self._dead is not None and self._q.empty():
                    _raise_producer_error(self._dead)
                if self._closed:
                    raise MXNetError(
                        "DevicePrefetcher closed while a consumer was "
                        "waiting on step "
                        f"{step} [mxnet_tpu.io.prefetch]")
                try:
                    item = self._q.get(timeout=0.2)
                except _queue.Empty:
                    continue
                gen, istep, batch, exc = item
                if exc is not None:
                    _raise_producer_error(exc)
                if gen != self._gen:
                    continue                     # pre-seek leftover
                break
        _metrics.PREFETCH_STALL_SECONDS.observe(time.perf_counter() - t0)
        _metrics.PREFETCH_QUEUE_DEPTH.set(self._q.qsize())
        if istep != step:
            raise MXNetError(
                f"prefetch stream out of order: expected step {step}, "
                f"got {istep} [mxnet_tpu.io.prefetch]")
        self._expect = step + 1
        return batch

    def _seek(self, step: int, salt: int) -> None:
        reason = "salt" if salt != self._salt else "seek"
        with self._lock:
            self._gen += 1
            self._next_step = int(step)
            self._salt = int(salt)
        self._expect = int(step)
        # stale batches are NOT drained here: the producer may enqueue a
        # fresh-generation batch between the gen bump and a drain, and
        # draining it would deadlock the stream one step ahead of the
        # consumer forever.  get() filters stale generations instead
        # (bounded by depth, so the memory overhang is one queue).
        _metrics.PREFETCH_INVALIDATED.labels(reason=reason).inc()

    def _drain(self) -> None:
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                return

    # -- iterable mode -------------------------------------------------------
    def __iter__(self) -> Iterator[Any]:
        if self.is_callable:
            raise MXNetError(
                "callable-mode DevicePrefetcher is consumed via "
                "get(step) — SPMDTrainer.fit does this automatically; "
                "wrap an iterable to use iter()")
        return _EpochIterator(self)

    # -- shutdown ------------------------------------------------------------
    def close(self) -> None:
        """Stop the producer and drop queued batches (idempotent)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._gen += 1
        self._drain()
        _metrics.PREFETCH_INVALIDATED.labels(reason="close").inc()
        _metrics.PREFETCH_QUEUE_DEPTH.set(0)
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:   # noqa: BLE001 - interpreter teardown
            pass
