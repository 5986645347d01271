"""Runtime metrics — process-wide counters, gauges, and histograms.

NEW capability beyond the reference (no leezu/mxnet analog): the
reference's observability stops at the profiler (one traced window) and
``Monitor`` (per-op stats for one tic/toc span).  Neither answers "what
has the runtime been doing over this whole training run" — recompiles,
collective traffic, step-time composition.  This module is that
substrate: a process-wide, thread-safe registry of labeled metric
families, instrumented at the framework's existing choke points:

* **dispatch** (``ndarray/register.py``): every op invocation counts
  into ``mxnet_ops_dispatched_total{op=...}``; the per-op executable
  cache reports hits (``mxnet_compile_hits_total``), and a
  ``jax.monitoring`` listener counts real XLA backend compiles into
  ``mxnet_compile_misses_total`` + ``mxnet_compile_seconds`` — a silent
  recompile storm becomes a visible counter, not a mystery slowdown.
* **engine** (``engine.py``): waitall barriers (count + latency),
  live-buffer registry size and sweeps, async-error translations.
* **collectives** (``kvstore.py`` / ``parallel/ring.py``): allreduce /
  allgather calls, wire bytes, wall time.  Eager collectives (kvstore)
  count per execution; traced collectives (ring attention inside a
  compiled step) count at trace time — one count per compiled program,
  noted under the ``traced="1"`` label.
* **training loop** (``gluon/trainer.py``, ``parallel/spmd.py``, the
  contrib estimator): per-step histograms split into data-wait /
  dispatch / device-sync, a steps/sec gauge, and the device-memory
  high-watermark where the backend exposes it.

Exposition: :func:`dump_json` (machine-readable), :func:`render_text`
(Prometheus text format), and an optional background logger thread
(``MXNET_METRICS_LOG_INTERVAL`` seconds; 0 = off).  ``reset()`` zeroes
every series so test suites stay order-independent.

The registry is always on: an increment is a dict lookup plus a locked
float add, orders of magnitude below the cost of the op dispatch it
counts.  Label cardinality is bounded per family
(``MXNET_METRICS_MAX_SERIES``): past the cap, new label combinations
collapse into a single ``_other_`` series rather than growing without
bound (a user loop dispatching generated op names must not OOM the
registry).
"""
from __future__ import annotations

import bisect
import json
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .base import MXNetError, getenv, register_env

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "dump_json", "render_text",
    "reset", "value", "start_logger", "stop_logger",
    "DEFAULT_BUCKETS", "exponential_buckets",
]

register_env("MXNET_METRICS_LOG_INTERVAL", 0,
             "Seconds between background dumps of the runtime metrics "
             "registry to the 'mxnet_tpu.metrics' logger (JSON, non-zero "
             "series only). 0 (default) disables the logger thread.")
register_env("MXNET_METRICS_MAX_SERIES", 512,
             "Per-family label-cardinality bound for the runtime metrics "
             "registry: past this many distinct label combinations, new "
             "ones collapse into a single '_other_' series (guards "
             "against unbounded registry growth from generated names).")

# Fixed exponential buckets: 100us .. ~52s, factor 2 — wide enough for
# everything from a single eager dispatch to a cold-compile train step.
DEFAULT_BUCKETS: Tuple[float, ...] = tuple(
    1e-4 * (2.0 ** i) for i in range(20))


def exponential_buckets(start: float, factor: float,
                        count: int) -> Tuple[float, ...]:
    """``count`` bucket bounds ``start, start*factor, ...`` — the
    prometheus-client helper, for histograms whose domain is not the
    DEFAULT_BUCKETS seconds range (e.g. serving batch sizes)."""
    if start <= 0 or factor <= 1 or count < 1:
        raise MXNetError(
            f"exponential_buckets needs start>0, factor>1, count>=1; "
            f"got ({start}, {factor}, {count})")
    return tuple(start * (factor ** i) for i in range(count))


def _validate_name(name: str) -> None:
    import re
    if not re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$", name):
        raise MXNetError(f"invalid metric name {name!r}")


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _Family:
    """Base: a named metric with a fixed label-key tuple and one child
    per observed label-value combination."""

    kind = "untyped"

    def __init__(self, name: str, doc: str,
                 labels: Sequence[str] = ()) -> None:
        _validate_name(name)
        self.name = name
        self.doc = " ".join(doc.split())
        self.label_names = tuple(labels)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], Any] = {}
        if not self.label_names:
            self._children[()] = self._new_child()

    # -- child management --------------------------------------------------
    def _new_child(self) -> Any:
        raise NotImplementedError

    def labels(self, *values: Any, **kv: Any) -> "_Family":
        """Return a bound single-series view (prometheus-client style)."""
        if kv:
            if values:
                raise MXNetError("pass label values positionally OR by "
                                 "keyword, not both")
            if set(kv) != set(self.label_names):
                raise MXNetError(
                    f"metric {self.name!r} expects labels "
                    f"{self.label_names}, got {sorted(kv)}")
            values = tuple(kv[k] for k in self.label_names)
        vals = tuple(str(v) for v in values)
        if len(vals) != len(self.label_names):
            raise MXNetError(
                f"metric {self.name!r} expects {len(self.label_names)} "
                f"label values {self.label_names}, got {len(vals)}")
        return _Bound(self, self._child(vals))

    def _child(self, vals: Tuple[str, ...]) -> Any:
        child = self._children.get(vals)
        if child is None:
            with self._lock:
                child = self._children.get(vals)
                if child is None:
                    cap = int(getenv("MXNET_METRICS_MAX_SERIES", 512))
                    if len(self._children) >= cap:
                        # cardinality guard: collapse the overflow into
                        # one sentinel series instead of growing forever
                        vals = ("_other_",) * len(self.label_names)
                        child = self._children.get(vals)
                        if child is not None:
                            return child
                    child = self._children[vals] = self._new_child()
        return child

    def _default(self) -> Any:
        if self.label_names:
            raise MXNetError(
                f"metric {self.name!r} has labels {self.label_names}; "
                "bind them with .labels(...) first")
        return self._children[()]

    def reset(self) -> None:
        with self._lock:
            if self.label_names:
                self._children.clear()
            else:
                self._children = {(): self._new_child()}

    # -- exposition --------------------------------------------------------
    def _series(self) -> List[Tuple[Tuple[str, ...], Any]]:
        with self._lock:
            return sorted(self._children.items())

    def to_json(self) -> Dict[str, Any]:
        out = {"type": self.kind, "doc": self.doc,
               "labels": list(self.label_names), "series": []}
        for vals, child in self._series():
            out["series"].append(
                {"labels": dict(zip(self.label_names, vals)),
                 **child.to_json()})
        return out

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.doc}",
                 f"# TYPE {self.name} {self.kind}"]
        for vals, child in self._series():
            lines.extend(child.render(self.name, self.label_names, vals))
        return lines


def _label_str(names: Sequence[str], vals: Sequence[str],
               extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(v)}"' for k, v in zip(names, vals)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Bound:
    """One series of a family, with the value methods of its kind."""

    __slots__ = ("_family", "_child")

    def __init__(self, family: _Family, child: Any) -> None:
        self._family = family
        self._child = child

    def inc(self, amount: float = 1.0) -> None:
        self._child.inc(self._family._lock, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._child.inc(self._family._lock, -amount)

    def set(self, v: float) -> None:
        self._child.set(self._family._lock, v)

    def observe(self, v: float, exemplar: Optional[str] = None) -> None:
        self._child.observe(self._family._lock, v, exemplar)

    @property
    def value(self) -> float:
        return self._child.value


class _CounterChild:
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, lock: threading.Lock, amount: float) -> None:
        if amount < 0:
            raise MXNetError("counters only go up; use a gauge")
        with lock:
            self.value += amount

    def to_json(self) -> Dict[str, Any]:
        return {"value": self.value}

    def render(self, name, label_names, vals) -> List[str]:
        return [f"{name}{_label_str(label_names, vals)} "
                f"{_format_value(self.value)}"]


class _GaugeChild(_CounterChild):
    def inc(self, lock: threading.Lock, amount: float) -> None:
        with lock:
            self.value += amount

    def set(self, lock: threading.Lock, v: float) -> None:
        with lock:
            self.value = float(v)


#: an exemplar older than this is replaced by the next offered one even
#: when slower observations were seen since — "most recent slow", not
#: "all-time max", so a bad p99 points at a trace that still exists
_EXEMPLAR_TTL_S = 60.0


class _HistogramChild:
    __slots__ = ("bounds", "counts", "sum", "count", "exemplar", "pref")

    def __init__(self, bounds: Tuple[float, ...],
                 pref: str = "max") -> None:
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        # (trace_id, value, unix time) of the most-extreme recent
        # observation that carried a trace id (tracing exemplar
        # linkage).  ``pref`` picks the direction: "max" keeps the
        # slowest/largest recent value (latency histograms), "min" the
        # smallest (e.g. the worst-accepting speculative step, where
        # LOW is the pathology worth a trace)
        self.exemplar: Optional[Tuple[str, float, float]] = None
        self.pref = pref

    def observe(self, lock: threading.Lock, v: float,
                exemplar: Optional[str] = None) -> None:
        v = float(v)
        idx = bisect.bisect_left(self.bounds, v)
        with lock:
            self.counts[idx] += 1
            self.sum += v
            self.count += 1
            if exemplar is not None:
                ex = self.exemplar
                now = time.time()
                extreme = ex is not None and (
                    v <= ex[1] if self.pref == "min" else v >= ex[1])
                if ex is None or extreme \
                        or now - ex[2] > _EXEMPLAR_TTL_S:
                    self.exemplar = (str(exemplar), v, now)

    def to_json(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "buckets": [[b, c] for b, c in
                        zip(list(self.bounds) + ["+Inf"],
                            _cumulative(self.counts))],
            "sum": self.sum, "count": self.count}
        ex = self.exemplar
        if ex is not None:
            out["exemplar"] = {"trace_id": ex[0], "value": ex[1],
                               "ts": ex[2]}
        return out

    def render(self, name, label_names, vals) -> List[str]:
        lines = []
        for b, c in zip(list(self.bounds) + ["+Inf"],
                        _cumulative(self.counts)):
            le = b if isinstance(b, str) else _format_value(b)
            le_pair = 'le="%s"' % le
            lines.append(
                f"{name}_bucket"
                f"{_label_str(label_names, vals, le_pair)} {c}")
        lines.append(f"{name}_sum{_label_str(label_names, vals)} "
                     f"{_format_value(self.sum)}")
        lines.append(f"{name}_count{_label_str(label_names, vals)} "
                     f"{self.count}")
        return lines


def _cumulative(counts: Sequence[int]) -> List[int]:
    out, acc = [], 0
    for c in counts:
        acc += c
        out.append(acc)
    return out


def _format_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class Counter(_Family):
    kind = "counter"

    def _new_child(self) -> _CounterChild:
        return _CounterChild()

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(self._lock, amount)

    @property
    def value(self) -> float:
        return self._default().value


class Gauge(_Family):
    kind = "gauge"

    def _new_child(self) -> _GaugeChild:
        return _GaugeChild()

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(self._lock, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default().inc(self._lock, -amount)

    def set(self, v: float) -> None:
        self._default().set(self._lock, v)

    @property
    def value(self) -> float:
        return self._default().value


class Histogram(_Family):
    kind = "histogram"

    def __init__(self, name: str, doc: str, labels: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None,
                 exemplar_pref: str = "max") -> None:
        bounds = tuple(sorted(float(b) for b in
                              (buckets if buckets is not None
                               else DEFAULT_BUCKETS)))
        if not bounds:
            raise MXNetError("histogram needs at least one bucket bound")
        if exemplar_pref not in ("max", "min"):
            raise MXNetError(
                f"exemplar_pref must be 'max' or 'min', got "
                f"{exemplar_pref!r}")
        self.bounds = bounds
        self.exemplar_pref = exemplar_pref
        super().__init__(name, doc, labels)

    def _new_child(self) -> _HistogramChild:
        return _HistogramChild(self.bounds, self.exemplar_pref)

    def observe(self, v: float, exemplar: Optional[str] = None) -> None:
        self._default().observe(self._lock, v, exemplar)

    @property
    def sum(self) -> float:
        return self._default().sum

    @property
    def count(self) -> int:
        return self._default().count


# Hot-path cache for the per-op dispatch counter: one dict lookup per
# dispatch (see inc_op).  reset() must drop it — its bound children
# point at pre-reset series objects.
_OP_CHILDREN: Dict[str, _Bound] = {}


class MetricsRegistry:
    """Process-wide named family registry with exposition."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._families: "Dict[str, _Family]" = {}

    def _register(self, cls, name: str, doc: str, labels=(),
                  **kw) -> _Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if not isinstance(fam, cls) or \
                        fam.label_names != tuple(labels):
                    raise MXNetError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind} with labels {fam.label_names}")
                return fam
            fam = cls(name, doc, labels, **kw)
            self._families[name] = fam
            return fam

    def counter(self, name: str, doc: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, doc, labels)

    def gauge(self, name: str, doc: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, doc, labels)

    def histogram(self, name: str, doc: str = "",
                  labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None,
                  exemplar_pref: str = "max") -> Histogram:
        return self._register(Histogram, name, doc, labels,
                              buckets=buckets,
                              exemplar_pref=exemplar_pref)

    def get(self, name: str) -> Optional[_Family]:
        with self._lock:
            return self._families.get(name)

    def reset(self) -> None:
        """Zero every series (registrations survive) — test isolation."""
        with self._lock:
            fams = list(self._families.values())
        for f in fams:
            f.reset()
        _OP_CHILDREN.clear()
        _BULK_REASON_CHILDREN.clear()
        _BWD_SEG_CHILDREN.clear()
        _BUILD_STAGE_CHILDREN.clear()

    def dump_json(self) -> Dict[str, Any]:
        with self._lock:
            fams = sorted(self._families.items())
        return {name: fam.to_json() for name, fam in fams}

    def render_text(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        with self._lock:
            fams = sorted(self._families.items())
        lines: List[str] = []
        for _, fam in fams:
            lines.extend(fam.render())
        return "\n".join(lines) + "\n"


REGISTRY = MetricsRegistry()


def counter(name: str, doc: str = "",
            labels: Sequence[str] = ()) -> Counter:
    """Get-or-create a counter family on the global registry."""
    return REGISTRY.counter(name, doc, labels)


def gauge(name: str, doc: str = "", labels: Sequence[str] = ()) -> Gauge:
    """Get-or-create a gauge family on the global registry."""
    return REGISTRY.gauge(name, doc, labels)


def histogram(name: str, doc: str = "", labels: Sequence[str] = (),
              buckets: Optional[Sequence[float]] = None,
              exemplar_pref: str = "max") -> Histogram:
    """Get-or-create a histogram family on the global registry."""
    return REGISTRY.histogram(name, doc, labels, buckets, exemplar_pref)


def dump_json() -> Dict[str, Any]:
    return REGISTRY.dump_json()


def render_text() -> str:
    return REGISTRY.render_text()


def reset() -> None:
    REGISTRY.reset()


def _peek(fam: _Family, labels: Dict[str, Any]) -> Any:
    """Read-only series lookup: unlike fam.labels(...), never
    instantiates a child, so probing a never-observed combination does
    not pollute the exposition or consume a cardinality slot."""
    if labels:
        if set(labels) != set(fam.label_names):
            return None
        vals = tuple(str(labels[k]) for k in fam.label_names)
    else:
        if fam.label_names:
            return None
        vals = ()
    with fam._lock:
        return fam._children.get(vals)


def value(name: str, /, **labels: Any) -> float:
    """Current value of a counter/gauge series (0.0 if never touched,
    or if the name is a histogram — use :func:`hist_stats` there) — the
    delta-reading helper tools build breakdowns from."""
    fam = REGISTRY.get(name)
    if fam is None or isinstance(fam, Histogram):
        return 0.0
    child = _peek(fam, labels)
    return float(child.value) if child is not None else 0.0


def hist_stats(name: str, /, **labels: Any) -> Tuple[float, int]:
    """(sum, count) of a histogram series (zeros if never observed)."""
    fam = REGISTRY.get(name)
    if fam is None or not isinstance(fam, Histogram):
        return 0.0, 0
    child = _peek(fam, labels)
    if child is None:
        return 0.0, 0
    return float(child.sum), int(child.count)


# ---------------------------------------------------------------------------
# Core instrumentation families (created eagerly so exposition shows the
# full surface even before first use)
# ---------------------------------------------------------------------------

OPS_DISPATCHED = counter(
    "mxnet_ops_dispatched_total",
    "Imperative op dispatches through ndarray.register.invoke, by op "
    "name.", labels=("op",))
COMPILE_MISSES = counter(
    "mxnet_compile_misses_total",
    "XLA backend compilations (jax.monitoring backend_compile events): "
    "every one is a traced program that missed all compile caches.")
COMPILE_HITS = counter(
    "mxnet_compile_hits_total",
    "Per-op executable-cache hits on the eager dispatch path (the call "
    "reused a compiled executable instead of tracing).")
COMPILE_PERSISTENT_HITS = counter(
    "mxnet_compile_persistent_hits_total",
    "Programs loaded from jax's persistent compilation cache "
    "(jax_compilation_cache_dir) instead of compiled — a cold start "
    "shows misses, a warm restart shows these.")
COMPILE_SECONDS = histogram(
    "mxnet_compile_seconds",
    "Wall time of XLA backend compilations (jax.monitoring).")
PROGRAM_BUILD_SECONDS = histogram(
    "mxnet_program_build_seconds",
    "Wall time of each stage of getting a compiled program, as jax "
    "reports it: 'trace' (Python tracing to a jaxpr), 'lower' (jaxpr to "
    "an MLIR module), 'compile' (the XLA backend) or 'load' (the same "
    "call answered from jax's persistent cache). By program and role: "
    "mxnet_tpu.tracing.programs().",
    labels=("stage",))
EXEC_CACHE_SIZE = gauge(
    "mxnet_exec_cache_size",
    "Entries in the per-op executable cache (ndarray.register).")

ENGINE_WAITALL = counter(
    "mxnet_engine_waitall_total",
    "waitall() barriers on outstanding device work.")
ENGINE_WAITALL_SECONDS = histogram(
    "mxnet_engine_waitall_seconds",
    "Wall time blocked inside waitall() barriers.")
ENGINE_LIVE_BUFFERS = gauge(
    "mxnet_engine_live_buffers",
    "Device arrays in the engine's live weak registry.")
ENGINE_SWEEPS = counter(
    "mxnet_engine_sweeps_total",
    "Dead-entry sweeps of the engine's weak registries.")
ENGINE_SYNC_ERRORS = counter(
    "mxnet_engine_sync_errors_total",
    "Async device errors translated to MXNetError at sync points.")

COLLECTIVE_CALLS = counter(
    "mxnet_collective_calls_total",
    "Collective operations by kind. Eager collectives (kvstore) count "
    "per execution; traced ones (ring attention) count per trace, "
    "marked traced=\"1\".", labels=("collective", "traced"))
COLLECTIVE_BYTES = counter(
    "mxnet_collective_bytes_total",
    "Payload bytes this process contributed to collectives, by kind.",
    labels=("collective", "traced"))
COLLECTIVE_SECONDS = histogram(
    "mxnet_collective_seconds",
    "Wall time of eager collective operations, by kind.",
    labels=("collective",))
KVSTORE_PUSHES = counter(
    "mxnet_kvstore_pushes_total",
    "KVStore push() calls (gradient reductions entering the store).")

STEP_SECONDS = histogram(
    "mxnet_step_seconds",
    "Training-step wall time at the trainer boundary (dispatch side: "
    "data placement + program dispatch; device sync is the separate "
    "mxnet_step_sync_seconds component).")
STEP_DATA_SECONDS = histogram(
    "mxnet_step_data_seconds",
    "Per-step time waiting on input data (loader wait + host->device "
    "placement).")
STEP_DISPATCH_SECONDS = histogram(
    "mxnet_step_dispatch_seconds",
    "Per-step time dispatching the training computation (returns before "
    "the device finishes).")
STEP_SYNC_SECONDS = histogram(
    "mxnet_step_sync_seconds",
    "Per-step time blocked on device results (loss fetch / metric "
    "update).")
TRAINER_STEP_SECONDS = histogram(
    "mxnet_trainer_step_seconds",
    "gluon.Trainer.step wall time (gradient reduction + optimizer "
    "update dispatch). The estimator/SPMD loop-level view is "
    "mxnet_step_seconds.")
STEPS_TOTAL = counter(
    "mxnet_steps_total", "Optimizer steps taken.")
STEPS_PER_SECOND = gauge(
    "mxnet_steps_per_second",
    "Inverse wall time of the most recent training step.")
DEVICE_MEM_HIGHWATER = gauge(
    "mxnet_device_mem_highwater_bytes",
    "Device memory high-watermark (peak_bytes_in_use) where the "
    "backend exposes memory_stats; 0 elsewhere.")

MONITOR_STAT = gauge(
    "mxnet_monitor_stat",
    "Latest scalar statistic per op output collected by mx.monitor."
    "Monitor (set at toc()).", labels=("name",))

BULK_SEGMENTS = counter(
    "mxnet_bulk_segments_total",
    "Pending eager-op segments flushed by the lazy bulking engine "
    "(mxnet_tpu/bulk.py), by flush reason: host_read (asnumpy/item/"
    "direct buffer access), max_ops (MXNET_BULK_MAX_OPS reached), "
    "unjittable (an op that cannot trace arrived), mutation (in-place "
    "write to a promised buffer), waitall (engine barrier), autograd "
    "(backward boundary / record-scope transition), cross_thread "
    "(another thread read a promised buffer), param_boundary (per-"
    "layer backward segmentation closed the recorded segment at a "
    "parameter boundary — MXNET_BULK_BACKWARD_SEGMENTS=param).",
    labels=("reason",))
BULK_CACHE_HITS = counter(
    "mxnet_bulk_seg_cache_hits_total",
    "Segment flushes that reused a compiled fused executable (segment-"
    "signature cache hit).")
BULK_CACHE_MISSES = counter(
    "mxnet_bulk_seg_cache_misses_total",
    "Segment flushes that traced + compiled a new fused executable. "
    "Steady-state training should report 0 new misses after warmup.")
BULK_CACHE_SIZE = gauge(
    "mxnet_bulk_seg_cache_size",
    "Compiled fused segment executables held by the bulking engine's "
    "signature cache (LRU-bounded).")
BULK_OPS_PER_SEGMENT = histogram(
    "mxnet_bulk_ops_per_segment",
    "Ops per flushed bulking segment (1 means the flush trigger arrived "
    "before a second op could join).",
    buckets=exponential_buckets(1.0, 2.0, 8))
BULK_BACKWARD_SEGMENTS = counter(
    "mxnet_bulk_backward_segments_total",
    "Per-layer backward-segmentation events under "
    "MXNET_BULK_BACKWARD_SEGMENTS=param (bulk.py), by reason: "
    "param_boundary (a recorded segment was cut because the op stream "
    "crossed a fresh attach_grad leaf with the coalescing floor met — "
    "its gradients will stream during backward; moves in lockstep "
    "with mxnet_bulk_segments_total{reason=param_boundary}, which "
    "counts the same cuts as flushes), coalesced (a parameter "
    "boundary was crossed but the segment's captured parameter bytes "
    "were still under the MXNET_KV_BUCKET_BYTES floor, so the layers "
    "share a segment — the decision the flush counter cannot see).",
    labels=("reason",))

# -- continuous-batching generation engine (serving/generation.py) ----------
GEN_SLOTS_ACTIVE = gauge(
    "mxnet_gen_slots_active",
    "Decode slots currently occupied by an in-flight generation "
    "sequence (<= MXNET_GEN_MAX_SLOTS).")
GEN_QUEUE_DEPTH = gauge(
    "mxnet_gen_queue_depth",
    "Generation requests waiting in the prefill admission queue (the "
    "decode 'queue' is the slot table itself — see "
    "mxnet_gen_slots_active).")
GEN_TOKENS_TOTAL = counter(
    "mxnet_gen_tokens_total",
    "Tokens produced by the generation engine, by phase: 'prefill' "
    "(the first token of each sequence, emitted by the prompt pass) "
    "and 'decode' (every token from the resident decode step).",
    labels=("phase",))
GEN_STEP_SECONDS = histogram(
    "mxnet_gen_step_seconds",
    "Wall time of one generation-engine model execution, by phase "
    "(prefill = one prompt admitted; decode = one step over ALL "
    "active slots, from the later of its dispatch and the previous "
    "step's tokens reaching the host to its own tokens reaching the "
    "host: what the step cost the loop) — the prefill/decode split of "
    "engine time.",
    labels=("phase",),
    buckets=exponential_buckets(0.0005, 2.0, 14))
GEN_TTFT_SECONDS = histogram(
    "mxnet_gen_ttft_seconds",
    "Time-to-first-token per generation request: submit to the first "
    "streamed token (queue wait + prefill).",
    buckets=exponential_buckets(0.001, 2.0, 14))
GEN_QUEUE_WAIT_SECONDS = histogram(
    "mxnet_gen_queue_wait_seconds",
    "Seconds a generation request waited in the admission queue: "
    "submit to the pop that hands it to prefill (the slot wait; TTFT "
    "minus this is the admission itself).",
    buckets=exponential_buckets(0.001, 2.0, 14))
GEN_ITERATIONS_TOTAL = counter(
    "mxnet_gen_iterations_total",
    "Decode-loop iterations executed (each runs the resident decode "
    "step once over every active slot).")
GEN_STEPS_AHEAD_TOTAL = counter(
    "mxnet_gen_steps_ahead_total",
    "Decode steps launched before the previous step's tokens were "
    "read back (fed by its token array on the device): the host's "
    "dispatch, emit and bookkeeping ran under the device's step.")
GEN_STEP_FALLBACKS_TOTAL = counter(
    "mxnet_gen_step_fallbacks_total",
    "Decode steps launched the serial way (previous tokens read back "
    "first), by what stood in the way of launching ahead: idle "
    "(nothing was in flight), finish (a resident sequence ended or "
    "was about to), admit (a free slot and a waiting request), cancel, "
    "spec (a speculating iteration). With "
    "mxnet_gen_steps_ahead_total it counts every step.",
    labels=("reason",))
GEN_ROW_BLOCKS_READ_TOTAL = counter(
    "mxnet_gen_row_blocks_read_total",
    "Position blocks of the cache's rows that decode steps fetched: a "
    "launch adds each slot's pos // block + 1 (a free slot rides at 0), "
    "from the host's own position vector. Only a family whose step "
    "reads by extent moves it (ops.pallas.decode_attention); over "
    "mxnet_gen_row_blocks_total it is the share of the bucket a step "
    "reads.")
GEN_ROW_BLOCKS_TOTAL = counter(
    "mxnet_gen_row_blocks_total",
    "Position blocks the same launches would have fetched reading "
    "every slot's whole capacity bucket: slots x bucket / block a "
    "launch.")
GEN_EXPERT_ASSIGNMENTS_TOTAL = counter(
    "mxnet_gen_expert_assignments_total",
    "(slot, choice) pairs that decode steps routed to an expert this "
    "model HOLDS, summed over layers: what the held experts' grouped "
    "product computed. Read back with each step's tokens. Only a family "
    "with routed experts moves it and the three counters below "
    "(serving.moe); over mxnet_gen_expert_slots_total it is the mean "
    "tokens a held expert a layer a step.")
GEN_EXPERT_OFFERED_TOTAL = counter(
    "mxnet_gen_expert_offered_total",
    "(slot, choice) pairs the same steps routed over ALL the experts: "
    "slots x experts per token x layers a step. Assignments over it is "
    "the share of the routing that fell on this model's share of the "
    "experts.")
GEN_EXPERTS_HIT_TOTAL = counter(
    "mxnet_gen_experts_hit_total",
    "Held experts with at least one token, summed over layers and "
    "steps: each is one expert's matrices a step has to read.")
GEN_EXPERT_SLOTS_TOTAL = counter(
    "mxnet_gen_expert_slots_total",
    "Held experts x layers a step: the base of the two ratios above.")
GEN_LOOP_STEPS_TOTAL = counter(
    "mxnet_gen_loop_steps_total",
    "Loop steps the decode steps launched made (a looped family, "
    "serving.loop: its whole stack of layers once is one loop step; "
    "over mxnet_gen_iterations_total it is the loop steps a token "
    "costs).")
GEN_DISCARDED_TOKENS_TOTAL = counter(
    "mxnet_gen_discarded_tokens_total",
    "Decode-step tokens computed for a slot whose stream had already "
    "ended when they were read (the step was launched before the host "
    "read the EOS of the one before it, or the consumer cancelled): "
    "never streamed, in no other token counter.")
GEN_ADMISSIONS_TOTAL = counter(
    "mxnet_gen_admissions_total",
    "Generation requests admitted into a decode slot (prefill ran).")
GEN_RETIREMENTS_TOTAL = counter(
    "mxnet_gen_retirements_total",
    "Generation sequences retired from their slot, by reason: eos / "
    "length (max-tokens) / error / cancelled.", labels=("reason",))
GEN_TOKENS_PER_SECOND = gauge(
    "mxnet_gen_tokens_per_second",
    "Aggregate decode throughput over the engine's most recent "
    "iteration window (streamed tokens across all slots).")
GEN_KV_BUCKET_LEN = gauge(
    "mxnet_gen_kv_bucket_len",
    "Current KV-cache capacity bucket (padded sequence length every "
    "slot's cache is allocated at).")
GEN_KV_MIGRATIONS_TOTAL = counter(
    "mxnet_gen_kv_migrations_total",
    "KV-cache capacity-bucket migrations (cache grew to the next "
    "power-of-two length bucket; each switches the engine to that "
    "bucket's pre-compiled decode step).")
GEN_CACHE_BYTES = gauge(
    "mxnet_gen_cache_bytes",
    "Device bytes the generation engine's slot cache has allocated, by "
    "kind of per-slot state: rows (K/V rows that grow with the "
    "sequence, in the bucket grid), window (K/V rows capped at the "
    "attention window), state (recurrent state of fixed size).",
    labels=("kind",))
GEN_CACHE_LIVE_BYTES = gauge(
    "mxnet_gen_cache_live_bytes",
    "Bytes of mxnet_gen_cache_bytes that the live slots' sequences "
    "hold, by kind: their resident rows, their window rows (at most "
    "the window), their state.", labels=("kind",))
GEN_STATE_INSTALLS_TOTAL = counter(
    "mxnet_gen_state_installs_total",
    "Admissions that installed fixed-size state (recurrent state, conv "
    "tail, window rows) into a slot beside the prompt's K/V rows.")
GEN_SAMPLED_TOKENS_TOTAL = counter(
    "mxnet_gen_sampled_tokens_total",
    "Tokens emitted by the generation engine, by decode method "
    "(greedy / sample / top_k / top_p) — the on-device sampler keeps "
    "every method inside the compiled step, so the split is free to "
    "observe.", labels=("method",))
GEN_PREFIX_HITS_TOTAL = counter(
    "mxnet_gen_prefix_cache_hits_total",
    "Generation admissions that reused a resident shared-prefix KV "
    "entry (rows copied into the slot instead of re-running prefill "
    "over the prefix).")
GEN_PREFIX_MISSES_TOTAL = counter(
    "mxnet_gen_prefix_cache_misses_total",
    "Generation admissions that found no resident prefix for a "
    "cacheable prompt and ran a full cold prefill (the prefix rows "
    "are inserted for the next request).")
GEN_PREFIX_EVICTIONS_TOTAL = counter(
    "mxnet_gen_prefix_cache_evictions_total",
    "Shared-prefix KV entries evicted (LRU among unreferenced entries "
    "once the cache exceeds MXNET_GEN_PREFIX_CACHE_SLOTS).")
GEN_PREFIX_ROWS = gauge(
    "mxnet_gen_prefix_cache_rows",
    "KV positions (padded prefix rows, summed over resident entries) "
    "currently held in the shared-prefix cache — the device-memory "
    "footprint is rows x layers x heads x head_dim x 2 (K and V).")
GEN_SPEC_PROPOSED_TOKENS_TOTAL = counter(
    "mxnet_gen_spec_proposed_tokens_total",
    "Draft tokens proposed by the speculative-decoding subsystem "
    "(serving/speculation.py): k per speculative slot per iteration, "
    "before the target model's verify pass accepts or rejects them.")
GEN_SPEC_ACCEPTED_TOKENS_TOTAL = counter(
    "mxnet_gen_spec_accepted_tokens_total",
    "Draft tokens ACCEPTED by the verify pass: the draft token equaled "
    "the target's own sampled token at that position under the "
    "request's counter-PRNG key (greedy requests compare against the "
    "argmax). The accept rule makes speculative output byte-identical "
    "to non-speculative output at the same seed.")
GEN_SPEC_REJECTED_TOKENS_TOTAL = counter(
    "mxnet_gen_spec_rejected_tokens_total",
    "Draft tokens rejected by the verify pass (everything proposed "
    "after the first mismatch is discarded and the KV rows it wrote "
    "roll back — see mxnet_gen_kv_rollbacks_total).")
GEN_SPEC_ACCEPT_RATE = gauge(
    "mxnet_gen_spec_accept_rate",
    "Fraction of proposed draft tokens accepted over the engine's "
    "lifetime (accepted / proposed; 0 until the first speculative "
    "iteration). The economics dial of speculative decoding: uplift "
    "~ (1 + k * accept_rate) tokens per target step minus draft cost.")
GEN_SPEC_ACCEPTED_PER_STEP = histogram(
    "mxnet_gen_spec_accepted_per_step",
    "Tokens emitted per speculative slot-step (1 bonus token + the "
    "accepted draft prefix; 1 means every draft was rejected). The "
    "exemplar carries the trace id of the WORST-accepting recent step "
    "(lowest value), so a sagging accept rate points at a concrete "
    "iteration trace.",
    buckets=exponential_buckets(1.0, 2.0, 6), exemplar_pref="min")
GEN_KV_ROLLBACKS_TOTAL = counter(
    "mxnet_gen_kv_rollbacks_total",
    "PagedKVCache.truncate() rollbacks: slot positions rewound after "
    "the verify pass rejected draft tokens (their speculatively "
    "written KV rows become invisible to the position mask and are "
    "overwritten by the next accepted token).")

# -- async device-prefetch input pipeline (io/prefetch.py) ------------------
PREFETCH_QUEUE_DEPTH = gauge(
    "mxnet_prefetch_queue_depth",
    "Device-resident batches currently queued ahead of the training "
    "step by the DevicePrefetcher (<= MXNET_PREFETCH_DEPTH). Pinned at "
    "0 while the consumer outruns the loader — pair with "
    "mxnet_prefetch_stall_seconds to tell which side is the "
    "bottleneck.")
PREFETCH_H2D_SECONDS = histogram(
    "mxnet_prefetch_h2d_seconds",
    "Per-batch host->device placement time inside the prefetch thread "
    "(sharded device_put / commit of the already-fetched batch). This "
    "work overlaps the in-flight step; it only costs wall-clock when "
    "it exceeds the step time.",
    buckets=exponential_buckets(0.0005, 2.0, 14))
PREFETCH_STALL_SECONDS = histogram(
    "mxnet_prefetch_stall_seconds",
    "Per-step time the TRAINING LOOP spent blocked waiting for the "
    "prefetcher to produce its batch — the key input-pipeline number: "
    "~0 means input is fully hidden behind device compute; a majority "
    "share of mxnet_step_seconds means the loader (or H2D) is the "
    "bottleneck.",
    buckets=exponential_buckets(0.0005, 2.0, 14))
PREFETCH_BATCHES_TOTAL = counter(
    "mxnet_prefetch_batches_total",
    "Batches fetched, placed on device, and queued by the "
    "DevicePrefetcher background thread.")
PREFETCH_INVALIDATED = counter(
    "mxnet_prefetch_invalidated_total",
    "Prefetched-batch invalidations (the queue is flushed and the "
    "producer reseeks), by reason: 'seek' (non-consecutive step "
    "request — checkpoint restore / resume), 'salt' (HealthGuard "
    "rewind perturbed the replay salt), 'close' (pipeline shutdown).",
    labels=("reason",))

# -- serving resilience (serving/server.py + serving/replica.py) ------------
SERVING_RECOVERIES_TOTAL = counter(
    "mxnet_serving_recoveries_total",
    "Generation sequences resurrected after a fault, by recovery site: "
    "'decode' (a decode-step fault — the sequence re-prefills "
    "prompt+emitted on a healthy replica and resumes), 'worker' (a "
    "slot-resident sequence evacuated from a dead worker replica), "
    "'queue' (a not-yet-admitted request requeued from a dead "
    "replica's admission queue).", labels=("site",))
SERVING_RECOVERED_TOKENS = counter(
    "mxnet_serving_recovered_tokens_total",
    "Tokens already emitted by sequences at the moment they were "
    "resurrected (the re-prefill work recovery pays; the TokenStream "
    "index dedupe guarantees clients never see them twice).")
SERVING_RECOVERY_SECONDS = histogram(
    "mxnet_serving_recovery_seconds",
    "Per-sequence recovery latency: fault observed to the resurrected "
    "sequence's next streamed token (re-queue wait + re-prefill).",
    buckets=exponential_buckets(0.001, 2.0, 14))
SERVING_STREAM_DUPES_DROPPED = counter(
    "mxnet_serving_stream_dupes_dropped_total",
    "Duplicate tokens dropped at the TokenStream index boundary (a "
    "recovered producer re-emitted an index the consumer already has). "
    "Nonzero means the dedupe guard did real work; clients still see "
    "each index exactly once.")
SERVING_DRAINING = gauge(
    "mxnet_serving_draining",
    "1 while the serving process is draining (SIGTERM received: "
    "admissions shed with 429, resident work finishing, readiness "
    "503 / liveness 200).")


def record_step(total: float, data: float = 0.0, dispatch: float = 0.0,
                sync: Optional[float] = None, count: int = 1) -> None:
    """Observe one training step's phase breakdown (seconds).  Called by
    the loop owners (SPMDTrainer.step, the estimator fit loop); tools
    read the sums back with :func:`hist_stats`.  ``count`` > 1 marks a
    fused multi-step program (one observation, N optimizer steps)."""
    STEP_SECONDS.observe(total)
    STEP_DATA_SECONDS.observe(data)
    STEP_DISPATCH_SECONDS.observe(dispatch)
    if sync is not None:
        STEP_SYNC_SECONDS.observe(sync)
    STEPS_TOTAL.inc(count)
    if total > 0:
        STEPS_PER_SECOND.set(count / total)


_HIGHWATER_LAST = [0.0]      # monotonic seconds of the last real query
_HIGHWATER_MIN_INTERVAL_S = 1.0


def record_device_highwater() -> None:
    """Update the device-memory high-watermark gauge if the backend
    exposes memory_stats (TPU does; XLA:CPU returns None).

    Sampled at most once per second: the peak is monotonic within a
    run, and on remote backends ``memory_stats()`` is a host<->device
    round-trip — per-step it re-serializes the very loop the async
    input pipeline unblocks."""
    try:
        now = time.monotonic()
        if now - _HIGHWATER_LAST[0] < _HIGHWATER_MIN_INTERVAL_S:
            return
        _HIGHWATER_LAST[0] = now
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            peak = stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0))
            if peak:
                DEVICE_MEM_HIGHWATER.set(float(peak))
    except Exception:   # noqa: BLE001 - backend-dependent surface
        pass


def inc_op(name: str) -> None:
    """Count one op dispatch (called from ndarray.register.invoke)."""
    b = _OP_CHILDREN.get(name)
    if b is None:
        b = _OP_CHILDREN[name] = OPS_DISPATCHED.labels(op=name)
    b.inc()


# Hot-path cache for per-reason segment-flush counters (same pattern as
# _OP_CHILDREN; reset() drops it).
_BULK_REASON_CHILDREN: Dict[str, _Bound] = {}


def inc_bulk_segment(reason: str) -> None:
    """Count one bulking-segment flush (called from bulk.Segment.flush)."""
    b = _BULK_REASON_CHILDREN.get(reason)
    if b is None:
        b = _BULK_REASON_CHILDREN[reason] = BULK_SEGMENTS.labels(
            reason=reason)
    b.inc()


# Hot-path cache for the backward-segmentation event counter (the cut
# decision runs once per recorded op append).
_BWD_SEG_CHILDREN: Dict[str, _Bound] = {}


def inc_backward_segment(reason: str) -> None:
    """Count one backward-segmentation event (bulk.try_append's
    param-boundary cut decision)."""
    b = _BWD_SEG_CHILDREN.get(reason)
    if b is None:
        b = _BWD_SEG_CHILDREN[reason] = BULK_BACKWARD_SEGMENTS.labels(
            reason=reason)
    b.inc()


# ---------------------------------------------------------------------------
# jax.monitoring bridge: real XLA backend compiles -> compile-miss counter
# ---------------------------------------------------------------------------

_JAX_HOOK = {"installed": False}
# Hot-path cache for the build-stage histogram: jax reports every inner
# jit of a program it traces, tens of thousands of events a large step.
_BUILD_STAGE_CHILDREN: Dict[str, Any] = {}
_HOOK_TLS = threading.local()


def _install_jax_hooks() -> None:
    if _JAX_HOOK["installed"]:
        return
    _JAX_HOOK["installed"] = True
    from jax import monitoring as _mon

    def _on_event(event: str, **kw: Any) -> None:
        # fires inside compile_or_get_cached, before the enclosing
        # backend_compile duration on the same thread: that "compile"
        # was a read from jax's persistent cache
        if event == "/jax/compilation_cache/cache_hits":
            _HOOK_TLS.cache_hit = True

    from . import tracing
    stages = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def _on_duration(event: str, duration: float, **kw: Any) -> None:
        stage = stages.get(event)
        if stage is None:
            return
        if stage == "compile":
            if getattr(_HOOK_TLS, "cache_hit", False):
                _HOOK_TLS.cache_hit = False
                COMPILE_PERSISTENT_HITS.inc()
                stage = "load"
            else:
                COMPILE_MISSES.inc()
                COMPILE_SECONDS.observe(duration)
        child = _BUILD_STAGE_CHILDREN.get(stage)
        if child is None:
            child = _BUILD_STAGE_CHILDREN[stage] = \
                PROGRAM_BUILD_SECONDS.labels(stage=stage)
        child.observe(duration)
        if "fun_name" in kw:
            tracing.note_build(stage, str(kw["fun_name"]), duration)

    _mon.register_event_listener(_on_event)
    _mon.register_event_duration_secs_listener(_on_duration)


_install_jax_hooks()


# ---------------------------------------------------------------------------
# Periodic logger thread (MXNET_METRICS_LOG_INTERVAL)
# ---------------------------------------------------------------------------

_LOGGER_STATE: Dict[str, Any] = {"thread": None, "stop": None}


def _nonzero_summary() -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, fam in dump_json().items():
        series = []
        for s in fam["series"]:
            if fam["type"] == "histogram":
                if s.get("count"):
                    series.append({"labels": s["labels"],
                                   "sum": round(s["sum"], 6),
                                   "count": s["count"]})
            elif s.get("value"):
                series.append({"labels": s["labels"],
                               "value": s["value"]})
        if series:
            out[name] = series
    return out


def start_logger(interval: Optional[float] = None) -> bool:
    """Start the background metrics logger (idempotent). Returns True if
    a thread is running after the call."""
    if interval is None:
        interval = float(getenv("MXNET_METRICS_LOG_INTERVAL", 0))
    if interval <= 0:
        return False
    if _LOGGER_STATE["thread"] is not None and \
            _LOGGER_STATE["thread"].is_alive():
        return True
    import logging
    log = logging.getLogger("mxnet_tpu.metrics")
    stop = threading.Event()

    def _run() -> None:
        while not stop.wait(interval):
            try:
                log.info("metrics %s", json.dumps(_nonzero_summary()))
            except Exception:   # noqa: BLE001 - never kill the app
                pass

    th = threading.Thread(target=_run, name="mxnet-metrics-logger",
                          daemon=True)
    _LOGGER_STATE["thread"], _LOGGER_STATE["stop"] = th, stop
    th.start()
    return True


def stop_logger() -> None:
    stop = _LOGGER_STATE["stop"]
    if stop is not None:
        stop.set()
    th = _LOGGER_STATE["thread"]
    if th is not None:
        th.join(timeout=2.0)
    _LOGGER_STATE["thread"] = _LOGGER_STATE["stop"] = None


if float(getenv("MXNET_METRICS_LOG_INTERVAL", 0)) > 0:
    start_logger()
