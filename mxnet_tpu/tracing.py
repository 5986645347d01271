"""Distributed tracing — sampled spans with cross-wire propagation.

The metrics registry answers "how much / how often" and the profiler
answers "where did time go in one manually-traced window"; this module
answers "why was *this* request slow" and "where did *this* step's 40ms
go".  It is an always-on span runtime in the OpenTelemetry shape but
with zero dependencies and a hot path cheap enough to leave enabled in
production:

* ``span(name, **attrs)`` — context manager *and* decorator.  The first
  span on a thread with no active trace starts one (head-sampled by
  ``MXNET_TRACE_SAMPLE``); nested spans parent automatically through a
  :mod:`contextvars` context.
* finished spans land in a fixed-size ring buffer
  (``MXNET_TRACE_BUFFER_SPANS``) via an atomic-append (one
  ``itertools.count`` fetch + one list-slot store — no lock on the
  record path).
* **tail retention**: a trace that lost the head-sampling coin flip
  still buffers its spans in a small per-trace pending list; if any of
  its spans errors or runs past ``MXNET_TRACE_SLOW_MS`` the whole trace
  is upgraded into the ring buffer.  Slow and failed traces therefore
  survive even 1% sampling — exactly the traces worth keeping.
* **propagation**: the active context rides a contextvar (so ordinary
  calls and nested spans need nothing), and is explicitly attachable
  across threads and queues — ``capture()`` a context where the work is
  submitted, ``attach(ctx)`` where it runs.  The W3C ``traceparent``
  form (``00-<32 hex trace>-<16 hex span>-<2 hex flags>``) crosses the
  HTTP front end and the parameter-server wire, so PS-side handling
  shows up as a remote child span in the worker's trace.
* **export**: :func:`export_trace_events` renders Chrome/Perfetto
  trace-event JSON in the exact shape :func:`mxnet_tpu.profiler.dump`
  writes (same clock epoch, same ``pid``/``tid`` convention), so one
  ``chrome://tracing`` load shows spans and profiled ops side by side.
  Both serving HTTP servers expose it at ``GET /v1/traces``;
  ``tools/trace_dump.py`` fetches or saves it from the CLI.  While the
  profiler is running, finished spans are additionally mirrored
  straight into its event list (category ``"trace"``) through a direct
  append — never through the op-dispatch layer, so spans cannot fire
  monitor hooks or inflate dispatch metrics.
* **device trace**: every span that is really opened also holds a
  ``jax.profiler.TraceAnnotation`` of its name, so any ``jax.profiler``
  session (``mx.profiler.start_xla_trace``, TensorBoard, chipbench's
  traced window) shows the program's spans on ``/host:CPU``, on the
  clock of the device's ``XLA Ops`` lines.  Outside a session the
  annotation is a flag read in the runtime.  Records keep
  ``time.perf_counter()`` seconds; retroactive intervals
  (:func:`record_span`) have no annotation.
* **the device's work, named**: the pure functions the compiled
  programs are traced from carry ``jax.named_scope`` paths out of one
  vocabulary (:data:`COMPONENTS`), and every compiled program of the
  serving and training paths is built through :func:`program`, which
  is ``jax.jit`` plus a line in the program table: what the program is
  (its ``role``), what each compiled shape cost to get
  (``trace_s`` / ``lower_s`` / ``compile_s`` or ``load_s``, from jax's
  own duration events) and, on demand only, which component each of
  its device instructions belongs to (:meth:`Program.scopes`).

Overhead contract: with ``MXNET_TRACE_SAMPLE=0`` tracing is fully off —
``span()`` returns a shared no-op after one flag read, and zero spans
are ever recorded.  On an untraced path (tracing on, but no active
trace at a child-only site) the cost is one contextvar read.  A
sampled-out span costs a couple of dict/list operations (≤ a few µs).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import os
import random
import re
import threading
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional, Set,
                    Tuple)

from . import profiler as _prof
from .base import getenv, register_env

__all__ = ["span", "child_span", "capture", "root_context", "attach",
           "current_context", "current_trace_id", "traceparent",
           "parse_traceparent", "record_span", "spans",
           "export_trace_events", "active_spans_tree", "configure",
           "reset", "SpanContext", "COMPONENTS", "ROLES", "Program",
           "program", "programs", "hlo_scopes"]

# ring capacity, a power of two: 90 s of the busiest measured producer
# stay resident (the serving engine under chipbench's
# gpt2_774m.serve_doc records 104 spans a second, the trainer 19;
# PERF.md, Findings PR 26), because chipbench reads the ring when the
# job has ended
_BUFFER_SPANS = 16384

register_env(
    "MXNET_TRACE_SAMPLE", 1.0,
    "Head-sampling probability per trace for the distributed-tracing "
    "span runtime (mxnet_tpu.tracing). 1.0 records every trace, 0 "
    "disables tracing entirely (spans become no-ops and nothing is "
    "recorded); in between, each new trace keeps its spans with this "
    "probability — except traces containing an error or a span slower "
    "than MXNET_TRACE_SLOW_MS, which are tail-upgraded and kept "
    "regardless.")
register_env(
    "MXNET_TRACE_BUFFER_SPANS", _BUFFER_SPANS,
    "Capacity of the in-process finished-span ring buffer. Oldest "
    "spans are overwritten; GET /v1/traces, tools/trace_dump.py and "
    "tracing.export_trace_events() export whatever is resident. A "
    "resident record holds about 1 KB (0.8-1.0 KB measured over the "
    "serving engine's spans), so the default is about 16 MB once the "
    "ring has filled.")
register_env(
    "MXNET_TRACE_SLOW_MS", 100.0,
    "Tail-retention threshold for the span runtime: a span that runs "
    "at least this many milliseconds (or exits with an exception) "
    "upgrades its whole trace into the ring buffer even when the "
    "trace lost the MXNET_TRACE_SAMPLE coin flip, so slow/failed "
    "traces survive low sample rates.")

# spans a not-yet-upgraded trace may hold in its pending list before the
# oldest are dropped (bounds memory for long-lived unsampled traces)
_PENDING_CAP = 256

_CTX: contextvars.ContextVar[Optional["SpanContext"]] = \
    contextvars.ContextVar("mxnet_trace_ctx", default=None)


class _Runtime:
    """Tracing configuration + the ring buffer (rebuilt by configure())."""

    __slots__ = ("sample", "cap", "slow_s", "buf", "seq", "rng",
                 "randbits")

    def __init__(self, sample: Optional[float] = None,
                 buffer_spans: Optional[int] = None,
                 slow_ms: Optional[float] = None) -> None:
        if sample is None:
            sample = float(getenv("MXNET_TRACE_SAMPLE", 1.0))
        if buffer_spans is None:
            buffer_spans = int(getenv("MXNET_TRACE_BUFFER_SPANS",
                                      _BUFFER_SPANS))
        if slow_ms is None:
            slow_ms = float(getenv("MXNET_TRACE_SLOW_MS", 100.0))
        self.sample = max(0.0, min(1.0, float(sample)))
        self.cap = max(1, int(buffer_spans))
        self.slow_s = max(0.0, float(slow_ms)) / 1e3
        self.buf: List[Optional[Dict[str, Any]]] = [None] * self.cap
        # one atomic fetch per finished span; the slot store is a plain
        # list item assignment — the append path takes no lock
        self.seq = itertools.count()
        self.rng = random.Random(os.urandom(8))
        self.randbits = self.rng.getrandbits


_RT = _Runtime()

# currently-open spans, span_id -> _Span (watchdog dumps walk this)
_OPEN: Dict[str, "_Span"] = {}


def configure(sample: Optional[float] = None,
              buffer_spans: Optional[int] = None,
              slow_ms: Optional[float] = None) -> None:
    """(Re)configure the runtime; unset arguments re-read their env
    vars.  Discards recorded spans (fresh ring buffer)."""
    global _RT
    _RT = _Runtime(sample, buffer_spans, slow_ms)


def reset() -> None:
    """Drop every recorded span and the program table with the jitted
    callables it keeps (keeps the current configuration)."""
    rt = _RT
    rt.buf = [None] * rt.cap
    rt.seq = itertools.count()
    with _PROGRAM_LOCK:
        _PROGRAMS.clear()
        _NEWEST.clear()


class _TraceState:
    """Mutable per-trace retention state shared by the trace's spans."""

    __slots__ = ("trace_id", "sampled", "upgraded", "dead", "pending",
                 "lock")

    def __init__(self, trace_id: str, sampled: bool) -> None:
        self.trace_id = trace_id
        self.sampled = sampled
        self.upgraded = False
        self.dead = False          # local root ended without retention
        self.pending: List[Dict[str, Any]] = []
        self.lock = threading.Lock()

    @property
    def recording(self) -> bool:
        return self.sampled or self.upgraded


class SpanContext:
    """Immutable propagation handle: (trace_id, span_id, shared state).

    ``capture()`` one where work is submitted; ``attach()`` it where the
    work runs (another thread, a queue consumer); ``traceparent`` is its
    W3C wire form.
    """

    __slots__ = ("trace_id", "span_id", "state")

    def __init__(self, trace_id: str, span_id: str,
                 state: _TraceState) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.state = state

    @property
    def sampled(self) -> bool:
        return self.state.recording

    @property
    def traceparent(self) -> str:
        flags = "01" if self.state.recording else "00"
        return f"00-{self.trace_id}-{self.span_id}-{flags}"

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"SpanContext(trace={self.trace_id[:8]}…, "
                f"span={self.span_id}, sampled={self.sampled})")


def _emit(rec: Dict[str, Any]) -> None:
    """Commit one finished-span record: ring append + profiler mirror."""
    rt = _RT
    i = next(rt.seq)
    rec["seq"] = i
    rt.buf[i % rt.cap] = rec
    if _prof._active["on"]:
        # direct event append (never via op dispatch: spans must not
        # fire monitor hooks or count as dispatched ops)
        t0 = _prof._P.t0
        _prof.record_span(
            rec["name"], (rec["t_begin"] - t0) * 1e6,
            (rec["t_end"] - t0) * 1e6, rec["tid"],
            {"trace_id": rec["trace_id"], "span_id": rec["span_id"]})


def _upgrade(st: _TraceState) -> None:
    """Tail-based retention: flush the trace's pending spans into the
    ring buffer and record everything that follows directly."""
    with st.lock:
        if st.upgraded:
            return
        st.upgraded = True
        st.dead = False
        pending, st.pending = st.pending, []
    for rec in pending:
        _emit(rec)


def _trace_id() -> str:
    return "%032x" % _RT.randbits(128)


def _span_id() -> str:
    return "%016x" % _RT.randbits(64)


_ANNOTATION: Any = None


def _annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, imported at the first span that
    is really opened (a process with tracing off never pays it)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _NoopSpan:
    """Shared do-nothing span (tracing off, or child-only miss)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def __call__(self, fn: Callable) -> Callable:
        return fn

    def add_link(self, trace_id: Optional[str]) -> None:
        pass

    def set_attr(self, **attrs: Any) -> None:
        pass

    trace_id = None
    span_id = None


_NOOP = _NoopSpan()


class _Span:
    """One live span: context manager and decorator."""

    __slots__ = ("name", "attrs", "links", "trace_id", "span_id",
                 "parent_id", "state", "t_begin", "error", "_token",
                 "_root", "_thread", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]) -> None:
        self.name = name
        self.attrs = attrs
        self.links: List[str] = []
        self.error: Optional[str] = None

    # -- decorator form ------------------------------------------------
    def __call__(self, fn: Callable) -> Callable:
        name, attrs = self.name, self.attrs

        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with span(name, **attrs):
                return fn(*args, **kwargs)
        return wrapper

    # -- context-manager form --------------------------------------------
    def __enter__(self) -> "_Span":
        parent = _CTX.get()
        if parent is None:
            rt = _RT
            st = _TraceState(_trace_id(), rt.rng.random() < rt.sample)
            self.parent_id = ""
            self._root = True
        else:
            st = parent.state
            self.parent_id = parent.span_id
            self._root = False
        self.state = st
        self.trace_id = st.trace_id
        self.span_id = _span_id()
        self._thread = threading.current_thread().name
        self._token = _CTX.set(
            SpanContext(self.trace_id, self.span_id, st))
        if not st.dead:
            _OPEN[self.span_id] = self
        # the same interval on the device trace's clock: a flag read
        # unless a jax.profiler session is running
        self._ann = _annotation()(self.name, **self.attrs)
        self._ann.__enter__()
        self.t_begin = time.perf_counter()
        return self

    def __exit__(self, et: Any, ev: Any, tb: Any) -> bool:
        t_end = time.perf_counter()
        self._ann.__exit__(et, ev, tb)
        _CTX.reset(self._token)
        _OPEN.pop(self.span_id, None)
        st = self.state
        if et is not None and self.error is None:
            self.error = f"{getattr(et, '__name__', et)}: {ev}"
        if not st.dead or st.recording:
            rec = self._record(t_end)
            if st.recording:
                _emit(rec)
            elif not st.dead:
                with st.lock:
                    st.pending.append(rec)
                    if len(st.pending) > _PENDING_CAP:
                        del st.pending[0]
                if self.error is not None \
                        or (t_end - self.t_begin) >= _RT.slow_s:
                    _upgrade(st)
        if self._root and not st.recording:
            # trace ended neither sampled nor upgraded: drop it
            st.dead = True
            with st.lock:
                st.pending = []
        return False

    def _record(self, t_end: float) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "t_begin": self.t_begin, "t_end": t_end,
            "tid": threading.get_ident() % 100000,
            "thread": self._thread, "attrs": self.attrs,
        }
        if self.links:
            rec["links"] = list(self.links)
        if self.error is not None:
            rec["status"] = "error"
            rec["error"] = self.error
        else:
            rec["status"] = "ok"
        return rec

    # -- span-local mutation -----------------------------------------------
    def add_link(self, trace_id: Optional[str]) -> None:
        """Link another trace (engine iteration -> resident requests)."""
        if trace_id:
            self.links.append(trace_id)

    def set_attr(self, **attrs: Any) -> None:
        self.attrs.update(attrs)


def span(name: str, **attrs: Any):
    """Open a span: ``with span("prefill", req=rid): ...`` or
    ``@span("checkpoint.save")``.  With no active trace this starts a
    new head-sampled one; nested calls parent automatically."""
    if _RT.sample <= 0.0:
        return _NOOP
    return _Span(name, attrs)


def child_span(name: str, **attrs: Any):
    """Like :func:`span` but never *starts* a trace: a no-op unless a
    trace is already active.  For hot internal sites (bulk flushes, kv
    wire ops) that should appear inside request/step traces without
    minting a trace of their own per call."""
    if _RT.sample <= 0.0 or _CTX.get() is None:
        return _NOOP
    return _Span(name, attrs)


def record_span(name: str, begin: float, end: float,
                ctx: Optional[SpanContext] = None,
                **attrs: Any) -> None:
    """Emit a span for an interval measured elsewhere (queue waits:
    begin/end are ``time.perf_counter()`` values).  ``ctx`` parents it;
    with ``ctx=None`` the currently-attached context is used, and with
    no trace active at all it is dropped."""
    if _RT.sample <= 0.0:
        return
    if ctx is None:
        ctx = _CTX.get()
    if ctx is None:
        return
    st = ctx.state
    if st.dead and not st.recording:
        return
    rec: Dict[str, Any] = {
        "name": name, "trace_id": ctx.trace_id,
        "span_id": _span_id(), "parent_id": ctx.span_id,
        "t_begin": begin, "t_end": end,
        "tid": threading.get_ident() % 100000,
        "thread": threading.current_thread().name,
        "attrs": attrs, "status": "ok",
    }
    if st.recording:
        _emit(rec)
    else:
        with st.lock:
            st.pending.append(rec)
            if len(st.pending) > _PENDING_CAP:
                del st.pending[0]
        if (end - begin) >= _RT.slow_s:
            _upgrade(st)


# ---------------------------------------------------------------------------
# context propagation
# ---------------------------------------------------------------------------

def current_context() -> Optional[SpanContext]:
    """The active span's context (None when untraced)."""
    return _CTX.get()


def current_trace_id() -> Optional[str]:
    """Trace id of the active trace — metric exemplars pass this."""
    ctx = _CTX.get()
    return ctx.trace_id if ctx is not None else None


def capture() -> Optional[SpanContext]:
    """Snapshot the active context for an explicit hand-off (store it
    on the queue item / request object at submit time)."""
    return _CTX.get()


def root_context() -> Optional[SpanContext]:
    """The context of a fresh head-sampled trace that has no span of its
    own — for work submitted with no active trace whose spans are all
    recorded where it runs (an in-process generation request: the
    engine thread records its ``queue.wait`` and ``engine.prefill``
    under :func:`attach`).  Like a remote parent's, its span id names
    no record.  None when tracing is off."""
    rt = _RT
    if rt.sample <= 0.0:
        return None
    st = _TraceState(_trace_id(), rt.rng.random() < rt.sample)
    return SpanContext(st.trace_id, _span_id(), st)


@contextlib.contextmanager
def attach(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Run the body under ``ctx`` (a :func:`capture` snapshot or a
    :func:`parse_traceparent` result).  ``attach(None)`` is a no-op, so
    call sites need no conditional."""
    if ctx is None:
        yield
        return
    token = _CTX.set(ctx)
    try:
        yield
    finally:
        _CTX.reset(token)


def traceparent() -> Optional[str]:
    """W3C ``traceparent`` header for the active context, or None."""
    ctx = _CTX.get()
    return ctx.traceparent if ctx is not None else None


def parse_traceparent(header: Optional[str]) -> Optional[SpanContext]:
    """Parse a ``00-<trace>-<span>-<flags>`` header into an attachable
    remote context (spans opened under it become remote children).
    Malformed input — or tracing off — returns None."""
    if not header or _RT.sample <= 0.0:
        return None
    parts = header.strip().lower().split("-")
    if len(parts) != 4:
        return None
    ver, tid, sid, flags = parts
    if len(ver) != 2 or len(tid) != 32 or len(sid) != 16 \
            or len(flags) != 2:
        return None
    try:
        int(tid, 16), int(sid, 16), int(flags, 16)
    except ValueError:
        return None
    if int(tid, 16) == 0 or int(sid, 16) == 0:
        return None
    st = _TraceState(tid, bool(int(flags, 16) & 1))
    return SpanContext(tid, sid, st)


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def spans(trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Recorded spans, oldest first (optionally one trace's)."""
    rt = _RT
    out = [r for r in list(rt.buf) if r is not None]
    if trace_id is not None:
        out = [r for r in out if r["trace_id"] == trace_id]
    out.sort(key=lambda r: r["seq"])
    return out


def export_trace_events() -> Dict[str, Any]:
    """Chrome/Perfetto trace-event JSON — byte-shape identical to the
    profiler's :func:`mxnet_tpu.profiler.dump` payload and on the same
    clock epoch, so one ``chrome://tracing`` / Perfetto load can show a
    profiler dump and this export side by side.  ``programs`` (a key
    the viewers ignore) is the program table, :meth:`Program.describe`
    of every line."""
    t0 = _prof._P.t0
    events: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": "mxnet_tpu"}},
    ]
    for rec in spans():
        args: Dict[str, Any] = {
            "trace_id": rec["trace_id"], "span_id": rec["span_id"],
            "parent_id": rec["parent_id"], "status": rec["status"],
            "thread": rec["thread"],
        }
        if rec.get("error"):
            args["error"] = rec["error"]
        if rec.get("links"):
            args["links"] = rec["links"]
        for k, v in rec["attrs"].items():
            args.setdefault(k, v if isinstance(
                v, (int, float, bool, str, type(None))) else str(v))
        events.append({
            "name": rec["name"], "cat": "trace", "ph": "X",
            "ts": (rec["t_begin"] - t0) * 1e6,
            "dur": max(0.0, (rec["t_end"] - rec["t_begin"]) * 1e6),
            "pid": 0, "tid": rec["tid"], "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "programs": [p.describe() for p in programs()]}


def active_spans_tree() -> List[str]:
    """The currently-open spans as indented text lines, grouped by
    trace — the hang watchdog appends this to its diagnostic dump so a
    stall names the span it wedged in.  Never raises."""
    try:
        now = time.perf_counter()
        open_spans = [s for s in list(_OPEN.values())
                      if getattr(s, "span_id", None) is not None]
        by_id = {s.span_id: s for s in open_spans}
        children: Dict[str, List[_Span]] = {}
        roots: List[_Span] = []
        for s in open_spans:
            if s.parent_id and s.parent_id in by_id:
                children.setdefault(s.parent_id, []).append(s)
            else:
                roots.append(s)
        roots.sort(key=lambda s: (s.trace_id, s.t_begin))
        lines: List[str] = []

        def walk(s: "_Span", depth: int) -> None:
            age_ms = (now - s.t_begin) * 1e3
            attrs = " ".join(f"{k}={v}" for k, v in s.attrs.items())
            lines.append(
                f"{'  ' * depth}{s.name} trace={s.trace_id[:8]} "
                f"span={s.span_id[:8]} +{age_ms:.0f}ms "
                f"thread={s._thread}" + (f" {attrs}" if attrs else ""))
            for c in sorted(children.get(s.span_id, []),
                            key=lambda x: x.t_begin):
                walk(c, depth + 1)

        for r in roots:
            walk(r, 0)
        return lines
    except Exception:   # noqa: BLE001 - diagnostics must never raise
        return []


# ---------------------------------------------------------------------------
# the device's work: component scopes and the program table
# ---------------------------------------------------------------------------

# The vocabulary of the ``jax.named_scope`` paths in the code the
# compiled programs are traced from: the first segment is the
# component, an optional second the part.  A cache read that feeds
# attention is ``attn/core``; ``norm`` is only for a norm that stands
# outside the other components (a LayerNorm fused to a projection is
# scoped with it); no path carries a layer index.  Gradient code needs
# nothing: jax writes ``jvp(ffn)/up`` and ``transpose(jvp(ffn))/up``
# around the same path.  tests/test_tracing.py holds every literal
# under mxnet_tpu/ to this tuple.
COMPONENTS = (
    "embed",
    "attn", "attn/qkv", "attn/core", "attn/out",
    "ffn", "ffn/up", "ffn/down",
    "experts", "experts/route", "experts/routed", "experts/shared",
    "ssm",
    "delta", "delta/conv", "delta/chunk", "delta/state",
    "norm",
    "cache", "cache/write", "cache/grow",
    "head",
    "sample",
    "loss",
    "optim",
)
UNSCOPED = "unscoped"

# what a compiled program is for, as :func:`program` is told
ROLES = ("train_step", "decode", "prefill", "prefill_suffix", "verify",
         "draft", "select", "cache_write", "cache_install", "cache_resize")

_STAGES = ("trace", "lower", "compile", "load")

_PROGRAM_LOCK = threading.Lock()
# (module, role, family) -> Program, in order of registration; a
# function nobody registered is (module, None, None)
_PROGRAMS: Dict[Tuple[str, Optional[str], Optional[str]], "Program"] = {}
# module -> the registered Program of that name that was put in last:
# where a build's events are booked when its trace-time body did not run
_NEWEST: Dict[str, "Program"] = {}
# the build this thread is in the middle of: ``current`` is (Program,
# shape entry) from the trace-time body to the compile's end,
# ``reading`` is true inside Program.hlo_text
_BUILD = threading.local()


def _module_name(fun_name: str) -> str:
    """The name the device trace's "XLA Modules" line shows for what
    jax's duration events call ``fun_name``: ``_step`` (the trace
    event) and ``jit(_step)`` (lowering, compiling) are both
    ``jit__step``."""
    name = _MODULE_NAMES.get(fun_name)
    if name is None:
        m = re.fullmatch(r"(\w+)\((.*)\)", fun_name)
        api, fn = (m[1], m[2]) if m else ("jit", fun_name)
        name = _MODULE_NAMES[fun_name] = \
            f"{api}_" + re.sub(r"[^\w.-]", "_", fn)
    return name


# jax reports a traced program's every inner jit (tens of thousands of
# events a large step): the listener's path is a few dict reads
_MODULE_NAMES: Dict[str, str] = {}


def _abstract(x: Any) -> Any:
    """What :meth:`Program.hlo_text` needs of one traced argument: shape,
    dtype and the (names, sizes) of the mesh it came in on, None where
    it came on none.  Anything without a shape is kept as it is (a
    static argument)."""
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    import jax
    mesh = getattr(getattr(jax.typeof(x), "sharding", None), "mesh", None)
    on = None if mesh is None or mesh.empty \
        else (tuple(mesh.axis_names), tuple(mesh.axis_sizes))
    return _Abstract(tuple(x.shape), x.dtype, on)


class _Abstract:
    __slots__ = ("shape", "dtype", "mesh")

    def __init__(self, shape: Tuple[int, ...], dtype: Any,
                 mesh: Optional[Tuple[Tuple[str, ...], Tuple[int, ...]]]
                 ) -> None:
        self.shape, self.dtype, self.mesh = shape, dtype, mesh

    def __str__(self) -> str:
        on = "@" + ",".join(self.mesh[0]) if self.mesh else ""
        return f"{self.dtype}[{','.join(map(str, self.shape))}]{on}"


def _signature(leaves: List[Any]) -> str:
    """A compiled shape in a line: each distinct array type (``@`` the
    axes of the mesh it came in on) with how many arguments have it,
    static arguments by their repr."""
    counts: Dict[str, int] = {}
    for leaf in leaves:
        key = str(leaf) if isinstance(leaf, _Abstract) else repr(leaf)
        counts[key] = counts.get(key, 0) + 1
    return ", ".join(k if n == 1 else f"{k} x{n}"
                     for k, n in counts.items())


class Program:
    """One line of the program table: a compiled program's ``module``
    (the name the device trace's "XLA Modules" line shows, e.g.
    ``jit__step``), its ``role`` (one of :data:`ROLES`; None for a
    function nobody registered), ``family``, the site's ``attrs``, and
    ``shapes``: one dict a compiled shape in order of arrival, with
    ``args`` (the shape in a line), the ``attrs`` of the instance that
    built it where it has any, and the seconds jax reported for its
    stages, ``trace_s``, ``lower_s`` and ``compile_s`` OR ``load_s``
    (the executable came out of jax's persistent cache).  An
    unregistered function has one entry for all its builds
    (``builds`` counts them).  An entry with ``reading`` true was
    built by :meth:`hlo_text` itself and is left out of every sum."""

    __slots__ = ("module", "role", "family", "attrs", "shapes", "jitted")

    def __init__(self, module: str, role: Optional[str] = None,
                 family: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None) -> None:
        self.module, self.role, self.family = module, role, family
        self.attrs = dict(attrs or {})
        self.shapes: List[Dict[str, Any]] = []
        self.jitted: Any = None

    def describe(self) -> Dict[str, Any]:
        """The line as plain data (what ``export_trace_events`` holds)."""
        return {"module": self.module, "role": self.role,
                "family": self.family, "attrs": dict(self.attrs),
                "shapes": [{k: v for k, v in e.items()
                            if not k.startswith("_")}
                           for e in self.shapes]}

    def built(self) -> List[Dict[str, Any]]:
        """The shapes the program's callers built (not ``hlo_text``)."""
        return [e for e in self.shapes if not e.get("reading")]

    def seconds(self, *stages: str) -> float:
        """Sum of ``stages`` (``"trace"``, ``"lower"``, ``"compile"``,
        ``"load"``; all four where none is given) over :meth:`built`."""
        return sum(e.get(f"{s}_s", 0.0) for e in self.built()
                   for s in (stages or _STAGES))

    # -- trace time ---------------------------------------------------------
    def _begin(self, args: Tuple[Any, ...], kwargs: Dict[str, Any]) -> None:
        """jax is tracing the program for a new shape on this thread."""
        import jax
        abstract = jax.tree_util.tree_map(_abstract, (args, kwargs))
        entry: Dict[str, Any] = {
            "args": _signature(jax.tree_util.tree_leaves(abstract)),
            "_abstract": abstract}
        if self.attrs:
            entry["attrs"] = dict(self.attrs)
        if getattr(_BUILD, "reading", False):
            entry["reading"] = True
        with _PROGRAM_LOCK:
            # back into the table after a reset(); under a newer
            # instance's line where one took the key since
            line = _PROGRAMS.setdefault(
                (self.module, self.role, self.family), self)
            _NEWEST.setdefault(self.module, line)
            if line is not self:
                del entry["_abstract"]
            line.shapes.append(entry)
        _BUILD.current = (line, entry)

    # -- from instruction to component, on demand ---------------------------
    def hlo_text(self, shape: int = -1) -> Optional[str]:
        """The optimized HLO text of :meth:`built` ``[shape]``: the
        registered callable lowered and compiled again from the shapes
        noted when it was traced, which jax answers from its caches (in
        this process from memory, else a load from the persistent one).
        Costs a program load at worst and runs nowhere unless asked:
        not at construction, in warm-up or on a step.  None for an
        unregistered function, for a shape inherited from an older
        instance, and for a program traced over a mesh of more than one
        device."""
        built = self.built()
        abstract = built[shape].get("_abstract") if built else None
        if self.jitted is None or abstract is None:
            return None
        import jax
        import numpy as _np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        meshes: Dict[Any, Any] = {}

        def concrete(leaf: Any) -> Any:
            if not isinstance(leaf, _Abstract):
                return leaf
            if leaf.mesh is None:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            if leaf.mesh not in meshes:
                names, sizes = leaf.mesh
                # every sharding over one device lowers alike
                meshes[leaf.mesh] = NamedSharding(Mesh(_np.asarray(
                    jax.devices()[:1]).reshape(sizes), names),
                    PartitionSpec())
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=meshes[leaf.mesh])

        leaves, treedef = jax.tree_util.tree_flatten(abstract)
        if any(isinstance(a, _Abstract) and a.mesh is not None
               and _np.prod(a.mesh[1]) > 1 for a in leaves):
            return None
        args, kwargs = treedef.unflatten([concrete(a) for a in leaves])
        _BUILD.reading = True
        try:
            return self.jitted.lower(*args, **kwargs).compile().as_text()
        finally:
            _BUILD.reading = False
            _BUILD.current = None

    def scopes(self, shape: int = -1
               ) -> Optional[Dict[str, Tuple[str, str, str]]]:
        """``{instruction name: (component, part, "fwd" | "bwd")}`` of
        the leaf instructions of :meth:`hlo_text` (:func:`hlo_scopes`
        has the rules); None where that is."""
        text = self.hlo_text(shape)
        return None if text is None else hlo_scopes(text)[0]


def program(fn: Callable, role: str, family: Optional[str] = None,
            attrs: Optional[Dict[str, Any]] = None,
            **jit_kwargs: Any) -> Any:
    """``jax.jit(fn, **jit_kwargs)`` with a line in the program table.

    Returns what ``jax.jit`` returns, so a call takes jit's own path.
    jit is handed ``fn`` under ``functools.wraps`` (the module's name,
    the argument names and with them every cache key stay what they
    were) with a body that runs only while jax traces, once a shape,
    and notes there the arguments' shapes and that this thread is now
    building this program.  The table keeps the jitted callable of the
    LAST instance registered under (module, ``role``, ``family``), and
    with it that model's closure, for :meth:`Program.hlo_text`; an
    older instance's shapes and seconds stay in the line, its callable
    does not.  :func:`reset` drops the table."""
    if role not in ROLES:
        raise ValueError(f"program role {role!r} is none of {ROLES}")
    import jax
    rec = Program(_module_name(fn.__name__), role, family, attrs)

    @functools.wraps(fn)
    def traced(*args: Any, **kwargs: Any) -> Any:
        rec._begin(args, kwargs)
        return fn(*args, **kwargs)

    rec.jitted = jax.jit(traced, **jit_kwargs)
    key = (rec.module, role, family)
    with _PROGRAM_LOCK:
        older = _PROGRAMS.get(key)
        if older is not None:
            rec.shapes = [{k: v for k, v in e.items() if k != "_abstract"}
                          for e in older.shapes]
        _PROGRAMS[key] = _NEWEST[rec.module] = rec
    return rec.jitted


def programs() -> List[Program]:
    """The program table: every registered program in order of
    registration, then a line (``role`` None) for each function jax
    built that nobody registered."""
    with _PROGRAM_LOCK:
        found = list(_PROGRAMS.values())
    return sorted(found, key=lambda p: p.role is None)


def note_build(stage: str, fun_name: str, seconds: float) -> None:
    """One stage of one build, as jax reported it to
    ``metrics._install_jax_hooks``'s listener on the thread that built:
    ``stage`` is ``"trace"``, ``"lower"``, ``"compile"`` or ``"load"``.
    Books the seconds in the table and emits the stage as a
    retroactive span ``program.<stage>`` under whatever span is open on
    this thread (nothing, outside a trace).  The helpers jax traces
    INSIDE a program (``_where``, ``_einsum``: thousands, microseconds
    each) are booked but get no span."""
    module = _module_name(fun_name)
    reading = getattr(_BUILD, "reading", False)
    current = getattr(_BUILD, "current", None)
    done = stage in ("compile", "load")
    with _PROGRAM_LOCK:
        if current is not None and current[0].module == module:
            rec, entry = current
            if done:
                _BUILD.current = None
        elif module in _NEWEST:
            # the trace-time body did not run: jax answered the trace
            # from its cache, or lowers one trace a second time
            rec = _NEWEST[module]
            if reading:
                entry = {"args": None, "reading": True}
                rec.shapes.append(entry)
                _BUILD.current = (rec, entry)
            else:
                if not rec.built():
                    rec.shapes.append({"args": None})
                entry = rec.built()[-1]
        elif reading:
            return
        else:
            rec = _PROGRAMS.setdefault((module, None, None),
                                       Program(module))
            if not rec.shapes:
                rec.shapes.append({"args": None, "builds": 0})
            entry = rec.shapes[0]
            entry["builds"] += done
        key = f"{stage}_s"
        entry[key] = entry.get(key, 0.0) + seconds
    if rec.role is not None or stage != "trace":
        end = time.perf_counter()
        record_span(f"program.{stage}", end - seconds, end,
                    program=module, role=rec.role)


# an instruction of the optimized HLO text: ``[ROOT] %name = <type>
# opcode(operands), attributes``; a tuple's type holds spaces
_HLO_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_HLO_OPCODE = re.compile(r"^([\w\-]+)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
# a segment jax writes for a jit inside the program: never a scope
_JIT_SEGMENT = re.compile(r"\b(?:jit|pjit|pmap)\([^()]*\)")
_TRANSFORM = re.compile(r"\w+\(")
_FIRST_SEGMENTS = frozenset(c for c in COMPONENTS if "/" not in c)
# instructions that only lead to other computations, and those that
# cost the device nothing
_CONTROL = ("while", "call", "conditional")
_FREE = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "partition-id", "replica-id"))


def _scope_of(op_name: str) -> Tuple[str, str, str]:
    """(component, part, direction) of a ``metadata.op_name``: the first
    vocabulary word of the path, the part where the next segment is one
    of that component's, ``bwd`` where the path holds ``transpose(``."""
    direction = "bwd" if "transpose(" in op_name else "fwd"
    path = _TRANSFORM.sub("", _JIT_SEGMENT.sub("", op_name))
    segments = path.replace(")", "").split("/")
    for i, seg in enumerate(segments):
        if seg in _FIRST_SEGMENTS:
            part = segments[i + 1] if i + 1 < len(segments) else ""
            if f"{seg}/{part}" not in COMPONENTS:
                part = ""
            return seg, part, direction
    return UNSCOPED, "", direction


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``=``: what follows the
    result type, which is one word or a parenthesised tuple."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = _HLO_OPCODE.match(rest)
    return m[1] if m else ""


def hlo_scopes(text: str) -> Tuple[Dict[str, Tuple[str, str, str]],
                                   Set[str]]:
    """From an optimized HLO module's text to ``({instruction name:
    (component, part, "fwd" | "bwd")}, mixed)`` for the leaf
    instructions of the ENTRY computation and of every computation a
    ``while``, ``call`` or ``conditional`` leads to from it; ``mixed``
    names the fusions whose instructions span more than one component.

    The rules: the component is the first word of :data:`COMPONENTS` in
    the instruction's ``metadata.op_name`` (:func:`_scope_of`); a
    ``fusion`` that holds a ``dot`` or a ``convolution`` takes THAT
    instruction's path (the optimizer's update fused behind a
    weight-gradient matmul counts with the matmul), else its root's,
    and where the root carries no word (a multi-output fusion's bare
    tuple), the one component its instructions name, if they name one;
    ``while``, ``call`` and ``conditional`` are not leaves (the trace
    shows them around their own children); an instruction whose path has
    no vocabulary word is ``unscoped``; one with no metadata at all (the
    compiler's own: a weight's prefetch and the wait for it, a layout
    copy) takes the scope of the first scoped instruction that waits
    for it, up to four steps on."""
    computations: Dict[str, List[Tuple[str, str, str, bool]]] = {}
    entry, body = None, None
    for line in text.splitlines():
        if body is None:
            m = _HLO_HEADER.match(line)
            if m:
                body = computations.setdefault(m[2], [])
                if m[1]:
                    entry = m[2]
        elif line.startswith("}"):
            body = None
        else:
            m = _HLO_INSTRUCTION.match(line)
            if m:
                body.append((m[2], _opcode(m[3]), m[3], bool(m[1])))

    def called(rest: str) -> List[str]:
        names = [m[2] for m in _HLO_CALLED.finditer(rest)]
        for m in _HLO_BRANCHES.finditer(rest):
            names += [n.strip().lstrip("%") for n in m[1].split(",")]
        return [n for n in names if n in computations]

    def op_name(rest: str) -> str:
        m = _HLO_OP_NAME.search(rest)
        return m[1] if m else ""

    def inside(comp: str, seen: Set[str]) -> List[Tuple[str, str, bool]]:
        """(opcode, op_name, is root) of a fused computation's
        instructions, those of the computations it calls first."""
        out: List[Tuple[str, str, bool]] = []
        if comp in seen:
            return out
        seen.add(comp)
        for _, opcode, rest, root in computations[comp]:
            if opcode == "fusion":
                for sub in called(rest):
                    out += [(o, n, False) for o, n, _ in inside(sub, seen)]
            out.append((opcode, op_name(rest), root))
        return out

    scopes: Dict[str, Tuple[str, str, str]] = {}
    mixed: Set[str] = set()
    visited: Set[str] = set()

    def walk(comp: str) -> None:
        if comp in visited:
            return
        visited.add(comp)
        bare: List[str] = []
        for name, opcode, rest, _ in computations[comp]:
            if opcode in _CONTROL:
                for sub in called(rest):
                    walk(sub)
                continue
            if opcode in _FREE or name in scopes:
                continue
            path = op_name(rest)
            subs = called(rest) if "calls=" in rest else []
            if subs:
                held = [x for sub in subs for x in inside(sub, set())]
                matmul = next((n for o, n, _ in held
                               if o in ("dot", "convolution") and n), None)
                root = next((n for _, n, r in held if r and n), None)
                path = matmul or root or path
                named = {_scope_of(n)[:2]: n for _, n, _ in held if n
                         and _scope_of(n)[0] != UNSCOPED}
                if len({c for c, _ in named}) > 1:
                    mixed.add(name)
                elif named and _scope_of(path)[0] == UNSCOPED:
                    path = next(iter(named.values()))
            scopes[name] = _scope_of(path)
            if not path:
                bare.append(name)
        if bare:
            # what the compiler put in (a weight's prefetch, a layout
            # copy): the scope of the first scoped instruction that
            # waits for it
            users: Dict[str, List[str]] = {}
            for name, _, rest, _ in computations[comp]:
                for operand in set(_HLO_OPERAND.findall(rest)):
                    users.setdefault(operand, []).append(name)
            for name in bare:
                front, seen = [name], {name}
                for _ in range(4):
                    front = [u for n in front for u in users.get(n, [])
                             if u not in seen and not seen.add(u)]
                    found = next((scopes[u] for u in front
                                  if scopes.get(u, (UNSCOPED,))[0]
                                  != UNSCOPED), None)
                    if found or not front:
                        break
                if found:
                    scopes[name] = found

    if entry is not None:
        walk(entry)
    return scopes, mixed
