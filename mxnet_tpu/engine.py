"""Execution-engine semantics over JAX's asynchronous dispatch.

Reference parity (leezu/mxnet): ``src/engine/threaded_engine*.cc``,
``include/mxnet/engine.h``. The reference's dependency engine exists so that
Python returns immediately while kernels run on device streams, with
correctness enforced by read/write var lists. XLA/PJRT gives the same
contract natively: every dispatched computation is asynchronous, ordered per
device stream, with data dependencies tracked by buffer futures. The
"engine" therefore shrinks to:

  * :func:`waitall`  — barrier on all outstanding device work
    (``Engine::WaitForAll`` / ``mx.nd.waitall``).
  * per-array ``wait_to_read`` — ``block_until_ready``
    (``Engine::WaitForVar``).
  * :func:`is_naive` — ``MXNET_ENGINE_TYPE=NaiveEngine`` forces a block
    after every op, the reference's standard first debugging step for
    suspected async races (SURVEY.md section 5.2).

Async errors: XLA poisons dependent buffers; blocking re-raises the original
error. :func:`_sync_and_translate` converts those into :class:`MXNetError`
at sync points, matching the reference's rethrow-at-sync behavior
(``src/engine/threaded_engine.cc`` OnCompleteStatic exception path).
"""
from __future__ import annotations

import time
import weakref
from typing import Any, Dict, Iterable

import jax

from . import metrics as _metrics
from .base import MXNetError, getenv

__all__ = ["waitall", "is_naive", "set_bulk_size", "bulk",
           "native_engine", "push_host_async"]

# Weak registry of live device arrays so waitall() can provide a true
# barrier. jax arrays are weakref-able but unhashable, so this is an
# id-keyed dict of weakrefs, swept when it grows past a bound.
_LIVE: Dict[int, "weakref.ref"] = {}
_SWEEP_AT = 4096


def is_naive() -> bool:
    """True when MXNET_ENGINE_TYPE=NaiveEngine (fully synchronous mode)."""
    return getenv("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice") == "NaiveEngine"


def _weak_register(registry: Dict[int, "weakref.ref"], arr: Any) -> None:
    """Insert ``arr`` into an id-keyed weakref registry, sweeping dead
    entries past the size bound."""
    try:
        registry[id(arr)] = weakref.ref(arr)
    except TypeError:  # plain numpy scalars etc. need no tracking
        pass
    if len(registry) > _SWEEP_AT:
        for k in [k for k, r in registry.items() if r() is None]:
            del registry[k]
        _metrics.ENGINE_SWEEPS.inc()
    if registry is _LIVE:
        _metrics.ENGINE_LIVE_BUFFERS.set(len(registry))


def track(arr: Any) -> Any:
    """Register a device array with the engine; blocks if in naive mode."""
    _weak_register(_LIVE, arr)
    if is_naive():
        _sync_and_translate(arr)
    return arr


def _sync_and_translate(arr: Any) -> Any:
    """Block on ``arr``; translate device-side errors into MXNetError."""
    try:
        return jax.block_until_ready(arr)
    except MXNetError:
        raise
    except Exception as exc:  # XLA raises XlaRuntimeError and friends
        _metrics.ENGINE_SYNC_ERRORS.inc()
        raise MXNetError(str(exc)) from exc


def waitall() -> None:
    """Block until all pushed device work completes (``mx.nd.waitall``)."""
    from . import bulk as _bulk   # lazy: bulk imports engine
    _bulk.flush_all("waitall")
    t0 = time.perf_counter()
    try:
        for key, ref in list(_LIVE.items()):
            arr = ref()
            if arr is not None:
                _sync_and_translate(arr)
            _LIVE.pop(key, None)
    finally:
        _metrics.ENGINE_WAITALL.inc()
        _metrics.ENGINE_WAITALL_SECONDS.observe(time.perf_counter() - t0)
        _metrics.ENGINE_LIVE_BUFFERS.set(len(_LIVE))


def wait(arrs: Iterable[Any]) -> None:
    for a in arrs:
        _sync_and_translate(a)


# ---------------------------------------------------------------------------
# Native host-work engine (src/engine.cc — ThreadedEngine analog).
# Device ordering belongs to XLA; this engine schedules *host* work (IO
# decode, custom ops, checkpoint writes) with the reference's read/write
# var dependency discipline.
# ---------------------------------------------------------------------------

def native_engine():
    """The shared native dependency engine, or None if libmxtpu.so is
    unavailable (``Engine::Get()`` analog; ``MXNET_ENGINE_TYPE`` and
    ``MXNET_CPU_WORKER_NTHREADS`` are honored at creation)."""
    from ._native import global_engine
    return global_engine()


def push_host_async(fn, read_vars=(), write_vars=(), priority: int = 0,
                    name: str = "") -> bool:
    """Push host work with var dependencies (``Engine::PushAsync``).

    Returns True if scheduled on the native engine, False if executed
    inline (no native library)."""
    eng = native_engine()
    if eng is None:
        fn()
        return False
    eng.push(fn, read_vars=read_vars, write_vars=write_vars,
             priority=priority, name=name)
    return True


# ---------------------------------------------------------------------------
# Bulking knobs (reference: MXNET_EXEC_BULK_EXEC_* + Engine::bulk_size).
# Since the lazy bulking engine (mxnet_tpu/bulk.py) these are LOAD-BEARING:
# the size is the pending-segment cap (MXNET_BULK_MAX_OPS at runtime).
# ---------------------------------------------------------------------------

def set_bulk_size(size: int) -> int:
    """Set the bulk-execution segment size — how many eager ops the lazy
    bulking engine fuses into one compiled dispatch; returns the previous
    value. ``size <= 1`` disables bulking (per-op dispatch)."""
    from . import bulk as _bulk_mod
    return _bulk_mod.set_max_ops(size)


class bulk:
    """Context manager scoping the bulk segment size (``mx.engine.bulk``).
    Exiting the scope flushes any segment still pending under it, so
    promised buffers never outlive the requested bulking window."""

    def __init__(self, size: int) -> None:
        self._size = size
        self._prev = None

    def __enter__(self) -> "bulk":
        self._prev = set_bulk_size(self._size)
        return self

    def __exit__(self, *exc: Any) -> None:
        from . import bulk as _bulk_mod
        _bulk_mod.flush_current("waitall")
        set_bulk_size(self._prev)
