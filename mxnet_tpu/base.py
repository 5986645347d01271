"""Base utilities: error types, env-var config tier, registry helpers.

Reference parity (leezu/mxnet):
  - ``python/mxnet/base.py`` (MXNetError, _LIB ctypes bootstrap)
  - ``3rdparty/dmlc-core`` env handling (``dmlc::GetEnv``) -> :func:`getenv`
  - ``src/c_api/c_api_error.cc`` error trampoline -> here errors are native
    Python exceptions; async device errors surface at sync points
    (see ``mxnet_tpu/engine.py``).

The env-var tier mirrors the reference's ``MXNET_*`` runtime config surface
(SURVEY.md section 5.6 tier 1).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "MXNetError",
    "NotImplementedForSymbol",
    "getenv",
    "register_env",
    "list_env",
    "classproperty",
    "join_distributed_job",
    "place_compile_cache",
]


def join_distributed_job() -> bool:
    """Join the multi-process job described by the launcher env
    (``tools/launch.py`` sets ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` — the DMLC_* rendezvous
    analog). Idempotent; no-op (returns False) when the env is absent or
    ``MXNET_NO_AUTO_DISTRIBUTED=1``. Must run before anything touches
    the XLA backend; raises MXNetError with guidance if it is too late.

    ``MXNET_DIST_INIT_TIMEOUT`` (seconds, default 120) bounds the wait
    for the coordinator so a stale env cannot hang an import forever.
    """
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not coord or os.environ.get("MXNET_NO_AUTO_DISTRIBUTED") == "1":
        return False
    import jax
    if jax.distributed.is_initialized():
        return True
    too_late = MXNetError(
        "the XLA backend was initialized before joining the "
        "multi-process job; import mxnet_tpu (or call "
        "jax.distributed.initialize) before any jax computation "
        "when JAX_COORDINATOR_ADDRESS is set — or set "
        "MXNET_NO_AUTO_DISTRIBUTED=1 to opt out")
    # A live XLA backend means initialize() is guaranteed to be too late
    from jax._src import xla_bridge as _xb
    if _xb.backends_are_initialized():
        raise too_late
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ.get("JAX_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
        initialization_timeout=int(
            os.environ.get("MXNET_DIST_INIT_TIMEOUT", "120")))
    return True


def place_compile_cache() -> None:
    """Give jax's persistent compilation cache a home — the ONE place
    this repo sets a compile-cache directory, run at ``import
    mxnet_tpu`` before anything compiles.

    A process pinned to the CPU backend (``JAX_PLATFORMS=cpu``: the
    tests and CPU smokes) is left at jax's default of no cache; the
    cache exists for cold starts on the chip.  Otherwise:

    * directory — ``JAX_COMPILATION_CACHE_DIR`` if set (jax reads it),
      else ``<checkout>/.jax_cache``: a fixed path (it is part of the
      cache key — a directory that moves never hits), git-ignored;
    * ``jax_persistent_cache_min_compile_time_secs`` 1.0 -> 0 (unless
      its own env var is set): sub-second programs were 45 of the 50 a
      warm gpt2_124m trainer start still compiled and 88 of the 151 of a
      warm generation-server start; caching them too took a warm
      ``chip_smoke.py`` from 132 s to 103 s on the v5e for 27 MB more
      cache (PR 21 chip runs).
    """
    import jax
    if jax.config.jax_platforms == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class MXNetError(RuntimeError):
    """Default error thrown by framework operations.

    Mirrors ``mxnet.base.MXNetError``. Errors raised inside asynchronously
    dispatched device computations are re-raised from sync points
    (``wait_to_read`` / ``asnumpy`` / ``waitall``), matching the reference
    engine's rethrow-at-sync semantics
    (``src/engine/threaded_engine.cc`` exception handling).
    """


class NotImplementedForSymbol(MXNetError):
    """Raised when an imperative-only API is used under symbolic tracing."""

    def __init__(self, function: Any, *args: Any) -> None:
        super().__init__(
            f"Function {getattr(function, '__name__', function)} is not "
            f"supported under hybridize tracing."
        )


# ---------------------------------------------------------------------------
# Env-var config tier (reference: docs/.../env_var.md, ~80 MXNET_* vars)
# ---------------------------------------------------------------------------

_ENV_REGISTRY: Dict[str, Dict[str, Any]] = {}
_ENV_LOCK = threading.Lock()


def register_env(name: str, default: Any, doc: str = "") -> None:
    """Register a recognized ``MXNET_*`` environment variable with default+doc.

    Powers :func:`list_env` (the analog of the reference's env_var.md page).
    """
    with _ENV_LOCK:
        _ENV_REGISTRY[name] = {"default": default, "doc": doc}


def getenv(name: str, default: Any = None, typ: Optional[type] = None) -> Any:
    """Read an environment variable with type coercion (``dmlc::GetEnv``)."""
    if name in _ENV_REGISTRY and default is None:
        default = _ENV_REGISTRY[name]["default"]
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is None:
        typ = type(default) if default is not None else str
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    try:
        return typ(raw)
    except (TypeError, ValueError):
        return default


def list_env() -> Dict[str, Dict[str, Any]]:
    """Return the registered env-var config surface (name -> default/doc)."""
    with _ENV_LOCK:
        return {k: dict(v) for k, v in _ENV_REGISTRY.items()}


# Env-dependent TRACE knobs (modules whose env var changes the traced
# program) register a poller here; gluon's graph_epoch() runs them all so
# a toggle between calls bumps the epoch — and thus every cached
# executable's key — even though no trace (where the knob would be read)
# has run.  Lives in base because every module can import base without a
# cycle.
_GRAPH_KNOB_POLLERS: List[Any] = []


def register_graph_knob(poll) -> None:
    """Register a zero-arg callable polled by ``gluon.block.graph_epoch``.
    It should compare the knob's current value to its last seen value and
    call ``gluon.block.invalidate_cached_graphs()`` on change."""
    _GRAPH_KNOB_POLLERS.append(poll)


def poll_graph_knobs() -> None:
    for _poll in _GRAPH_KNOB_POLLERS:
        _poll()


# Core runtime vars (more are registered at their use sites).
register_env("MXNET_NO_AUTO_DISTRIBUTED", 0,
             "Set to 1 to skip the automatic jax.distributed.initialize "
             "at import even when JAX_COORDINATOR_ADDRESS is present in "
             "the environment (single-process debugging of a node from "
             "a launcher-described job).")
register_env("MXNET_DIST_INIT_TIMEOUT", 120,
             "Seconds the import-time join of a launcher-described "
             "multi-process job waits for the coordinator before "
             "failing loudly — a stale JAX_COORDINATOR_ADDRESS cannot "
             "hang an import forever.")
register_env("MXNET_SANITIZE", "",
             "Comma-separated runtime sanitizers to install at import. "
             "'locks' patches threading.Lock/RLock creation so every "
             "lock allocated from this repo records per-thread "
             "acquisition stacks and a global acquired-while-holding "
             "graph; a lock-order inversion (the A/B-B/A deadlock "
             "pattern) is reported with both stacks. CI enables it on "
             "the chaos and resilience smokes. See "
             "docs/static_analysis.md.")
register_env("MXNET_SANITIZE_LOCKS_ACTION", "raise",
             "What the lock-order sanitizer does on an inversion: "
             "'raise' (default) raises LockOrderViolation at the "
             "offending acquisition; 'warn' prints the report to "
             "stderr and continues (for surveying a long run).")
register_env("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice",
             "Execution mode: 'NaiveEngine' forces synchronous per-op "
             "execution (block_until_ready after every op) for debugging; "
             "anything else uses async XLA dispatch.")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", 1,
             "Parity alias: the lazy bulking engine (mxnet_tpu/bulk.py, "
             "MXNET_BULK_MAX_OPS) is the load-bearing control for eager "
             "segment bulking; engine.set_bulk_size/engine.bulk scope it "
             "at runtime. This reference-named flag remains accepted but "
             "unread.")
register_env("MXNET_ENFORCE_DETERMINISM", 0,
             "Restrict to deterministic kernels.")


class classproperty:  # noqa: N801 - decorator naming
    """Read-only class-level property helper."""

    def __init__(self, fget: Callable[[Any], Any]) -> None:
        self.fget = fget

    def __get__(self, obj: Any, owner: Optional[type] = None) -> Any:
        return self.fget(owner)
