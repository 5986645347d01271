"""Profiler — op instrumentation, Chrome-trace dump, aggregate stats.

Reference parity (leezu/mxnet): ``src/profiler/profiler.{h,cc}`` (singleton
``Profiler``, engine hooks around Opr execution, per-device stats,
chrome://tracing JSON dump, ``AggregateStats`` tables) and the Python
surface ``python/mxnet/profiler.py`` (``set_config``/``set_state``/
``pause``/``resume``/``dump``/``dumps``, ``ProfileTask``/``ProfileEvent``/
``ProfileCounter``/``ProfileFrame``/``ProfileDomain``).

Design (tpu-first): ops are instrumented at the one dispatch point
(``ndarray.register.invoke``); device-side detail comes from wrapping the
XLA profiler (``start_xla_trace``/``stop_xla_trace`` → TensorBoard xplane,
the TPU analog of the reference's NVTX emitter), which
``device_summary`` reduces to device seconds by program role and by
component of the model (``tracing.COMPONENTS``). Eager timings measure
dispatch by default (the reference likewise measures engine-op execution,
not python); set ``MXNET_PROFILER_SYNC=1`` to block per op and capture
true device latency.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .base import MXNetError, getenv, register_env

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "reset", "state",
           "ProfileDomain", "ProfileTask", "ProfileEvent", "ProfileCounter",
           "ProfileFrame", "ProfileMarker", "scope",
           "start_xla_trace", "stop_xla_trace", "device_summary",
           "summarize_planes"]

register_env("MXNET_PROFILER_AUTOSTART", 0,
             "Start the profiler at import time (1 = on).")
register_env("MXNET_PROFILER_SYNC", 0,
             "Block after each profiled op to capture device latency.")

# checked on the hot dispatch path (mirrors register._amp_state pattern)
_active = {"on": False}

_LOCK = threading.Lock()


class _ProfilerState:
    def __init__(self) -> None:
        self.filename = "profile.json"
        self.profile_all = False
        self.profile_symbolic = True
        self.profile_imperative = True
        self.profile_memory = False
        self.profile_api = False
        self.aggregate_stats = True
        self.continuous_dump = False
        self.running = False
        self.paused = False
        self.events: List[Dict[str, Any]] = []
        self.agg: Dict[str, Dict[str, float]] = {}
        self.t0 = time.perf_counter()


_P = _ProfilerState()


def set_config(**kwargs: Any) -> None:
    """Configure the profiler (reference ``profiler.set_config``); accepts
    filename, profile_all, profile_symbolic, profile_imperative,
    profile_memory, profile_api, aggregate_stats, continuous_dump."""
    allowed = {"filename", "profile_all", "profile_symbolic",
               "profile_imperative", "profile_memory", "profile_api",
               "aggregate_stats", "continuous_dump"}
    for k, v in kwargs.items():
        if k not in allowed:
            raise MXNetError(f"profiler.set_config: unknown key {k!r} "
                             f"(allowed: {sorted(allowed)})")
        setattr(_P, k, v)


def state() -> str:
    return "run" if _P.running else "stop"


def _sync_flags() -> None:
    on = _P.running and not _P.paused
    _active["on"] = on
    from .ndarray import register as _reg
    _reg._profiler_state["on"] = on


def set_state(new_state: str = "stop") -> None:
    if new_state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop'")
    _P.running = new_state == "run"
    _P.paused = False
    _sync_flags()
    if _P.running and not _P.events:
        _P.t0 = time.perf_counter()


def start() -> None:
    set_state("run")


def stop() -> None:
    set_state("stop")


def pause() -> None:
    _P.paused = True
    _sync_flags()


def resume() -> None:
    _P.paused = False
    _sync_flags()


def reset() -> None:
    _P.events.clear()
    _P.agg.clear()
    _P.t0 = time.perf_counter()


def _now_us() -> float:
    return (time.perf_counter() - _P.t0) * 1e6


def record_op(name: str, begin_us: float, end_us: float,
              category: str = "operator") -> None:
    """Append one op execution record (called from register.invoke)."""
    with _LOCK:
        _P.events.append({"name": name, "cat": category, "ph": "X",
                          "ts": begin_us, "dur": end_us - begin_us,
                          "pid": 0, "tid": threading.get_ident() % 100000})
        a = _P.agg.setdefault(name, {"count": 0, "total": 0.0,
                                     "min": float("inf"), "max": 0.0})
        d = end_us - begin_us
        a["count"] += 1
        a["total"] += d
        a["min"] = min(a["min"], d)
        a["max"] = max(a["max"], d)


def record_span(name: str, begin_us: float, end_us: float,
                tid: Optional[int] = None,
                args: Optional[Dict[str, Any]] = None) -> None:
    """Mirror one finished tracing span into the profiler's event list
    (category ``"trace"``) so a single dump shows spans and ops on one
    timeline.  This is a direct event append — it never goes through
    the op-dispatch layer, so spans cannot fire monitor hooks, count as
    dispatched ops, or double-publish into ``mxnet_monitor_stat``."""
    ev: Dict[str, Any] = {
        "name": name, "cat": "trace", "ph": "X", "ts": begin_us,
        "dur": max(0.0, end_us - begin_us), "pid": 0,
        "tid": threading.get_ident() % 100000 if tid is None else tid}
    if args:
        ev["args"] = args
    with _LOCK:
        _P.events.append(ev)


class _OpTimer:
    """Context used by the dispatch hook."""

    __slots__ = ("name", "begin")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "_OpTimer":
        self.begin = _now_us()
        return self

    def __exit__(self, *exc: Any) -> None:
        if getenv("MXNET_PROFILER_SYNC", 0):
            from . import engine
            engine.waitall()
        record_op(self.name, self.begin, _now_us())


def op_timer(name: str) -> Optional[_OpTimer]:
    if not _active["on"]:
        return None
    return _OpTimer(name)


def dump(finished: bool = True) -> str:
    """Write accumulated events as chrome://tracing JSON; returns path."""
    payload = {
        "traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 0,
             "args": {"name": "mxnet_tpu"}},
            *_P.events,
        ],
        "displayTimeUnit": "ms",
    }
    with open(_P.filename, "w") as f:
        json.dump(payload, f)
    if finished:
        reset()
    return _P.filename


def dumps(reset_stats: bool = False) -> str:
    """Aggregate per-op summary table (reference ``AggregateStats``)."""
    lines = [f"{'Name':<40}{'Count':>8}{'Total(us)':>14}"
             f"{'Min(us)':>12}{'Max(us)':>12}{'Avg(us)':>12}"]
    with _LOCK:
        for name, a in sorted(_P.agg.items(),
                              key=lambda kv: -kv[1]["total"]):
            avg = a["total"] / max(a["count"], 1)
            lines.append(f"{name:<40}{int(a['count']):>8}"
                         f"{a['total']:>14.1f}{a['min']:>12.1f}"
                         f"{a['max']:>12.1f}{avg:>12.1f}")
        if reset_stats:
            _P.agg.clear()
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# User-level markers (reference: c_api_profile.cc objects)
# ---------------------------------------------------------------------------

class ProfileDomain:
    """Named grouping for marker objects (reference ``ProfileDomain``)."""

    def __init__(self, name: str) -> None:
        self.name = name


class ProfileTask:
    """start()/stop() span attributed to a domain."""

    def __init__(self, name: str, domain: Optional[ProfileDomain] = None) -> None:
        self.name = name
        self.domain = domain
        self._begin: Optional[float] = None

    def start(self) -> None:
        self._begin = _now_us()

    def stop(self) -> None:
        if self._begin is None:
            raise MXNetError(f"ProfileTask {self.name!r}: stop before start")
        cat = self.domain.name if self.domain else "task"
        record_op(self.name, self._begin, _now_us(), category=cat)
        self._begin = None

    def __enter__(self) -> "ProfileTask":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


ProfileFrame = ProfileTask  # frames are tasks that may nest (same record)


class ProfileEvent(ProfileTask):
    """Instant or spanning user event."""

    def mark(self) -> None:
        t = _now_us()
        with _LOCK:
            _P.events.append({"name": self.name, "cat": "event", "ph": "i",
                              "ts": t, "pid": 0, "s": "g",
                              "tid": threading.get_ident() % 100000})


class ProfileCounter:
    """Named counter emitted into the trace (reference ProfileCounter)."""

    def __init__(self, name: str, domain: Optional[ProfileDomain] = None) -> None:
        self.name = name
        self.value = 0

    def set_value(self, value: float) -> None:
        self.value = value
        with _LOCK:
            _P.events.append({"name": self.name, "ph": "C", "ts": _now_us(),
                              "pid": 0, "args": {self.name: value}})

    def increment(self, delta: float = 1) -> None:
        self.set_value(self.value + delta)

    def decrement(self, delta: float = 1) -> None:
        self.set_value(self.value - delta)

    def __iadd__(self, delta: float) -> "ProfileCounter":
        self.increment(delta)
        return self

    def __isub__(self, delta: float) -> "ProfileCounter":
        self.decrement(delta)
        return self


class ProfileMarker(ProfileEvent):
    pass


class scope:
    """``with profiler.scope('phase'):`` convenience span."""

    def __init__(self, name: str) -> None:
        self._task = ProfileTask(name)

    def __enter__(self) -> "scope":
        self._task.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._task.stop()


# ---------------------------------------------------------------------------
# XLA device-side tracing (TPU analog of the NVTX emitter)
# ---------------------------------------------------------------------------

_xla_trace_dir: Optional[str] = None


def start_xla_trace(logdir: str = "/tmp/mxnet_tpu_trace") -> None:
    """Start the XLA/xplane profiler; view in TensorBoard."""
    global _xla_trace_dir
    import jax
    jax.profiler.start_trace(logdir)
    _xla_trace_dir = logdir


def stop_xla_trace() -> Optional[str]:
    global _xla_trace_dir
    import jax
    jax.profiler.stop_trace()
    d, _xla_trace_dir = _xla_trace_dir, None
    return d


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def device_summary(xplane_path: str, programs: Optional[List[Any]] = None,
                   window: Optional[Tuple[int, int]] = None
                   ) -> Dict[str, Any]:
    """Reduce a device trace to seconds by program role and by component.

    ``xplane_path`` is an ``.xplane.pb`` or a directory that holds one
    (the log directory of ``start_xla_trace``: the newest is read).
    Each "XLA Ops" event is looked up in the program whose "XLA
    Modules" event encloses it on that device, so two programs that
    both have a ``fusion.1`` are told apart; the instruction's
    component comes from ``Program.scopes`` of ``programs`` (the
    process's own ``tracing.programs()`` by default: a v5e trace's
    events carry no ``op_name``, only the instruction's text), which
    costs a lowering and a cache hit a compiled shape.  For a builder
    after a traced run, never on a hot path.  ``window`` (start_ns,
    end_ns on the trace's clock) cuts the events to it.
    :func:`summarize_planes` describes the result."""
    import glob
    from jax.profiler import ProfileData
    if os.path.isdir(xplane_path):
        files = glob.glob(os.path.join(xplane_path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise MXNetError(f"no .xplane.pb under {xplane_path}")
        xplane_path = max(files, key=os.path.getmtime)
    planes: Dict[str, Dict[str, List[Tuple[str, int, int]]]] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if _DEVICE_PLANE.match(plane.name):
            planes[plane.name] = {
                line.name: [(ev.name, int(ev.start_ns),
                             int(ev.duration_ns)) for ev in line.events]
                for line in plane.lines
                if line.name in ("XLA Ops", "XLA Modules")}
    return summarize_planes(planes, programs, window)


def summarize_planes(planes: Dict[str, Dict[str, List[Tuple[str, int,
                                                            int]]]],
                     programs: Optional[List[Any]] = None,
                     window: Optional[Tuple[int, int]] = None
                     ) -> Dict[str, Any]:
    """:func:`device_summary` on plain data, ``{"/device:TPU:0": {"XLA
    Ops": [(instruction text or name, start_ns, dur_ns)], "XLA Modules":
    [("jit__step(<fingerprint>)", start_ns, dur_ns)]}}``.

    Returns seconds meaned over the devices: ``ops_s`` (every leaf
    instruction: a ``while``, ``call`` or ``conditional`` encloses its
    children on the line and is left out); ``by_role`` {role: seconds};
    ``by_component`` {"ffn/up fwd": seconds, ..., "unscoped fwd": ...};
    ``by_program`` {module: {"role", "runs", "seconds",
    "by_component"}}; ``mixed_s``, the seconds in fusions whose
    instructions span more than one component (booked by the fusion
    rule of ``tracing.hlo_scopes``), and ``mixed_pct`` of ``ops_s``.
    A module with several compiled shapes is read with the shape whose
    instructions name most of what ran under that fingerprint."""
    import bisect
    from . import tracing
    if programs is None:
        programs = tracing.programs()
    by_module: Dict[str, List[Any]] = {}
    for prog in programs:
        if prog.role is not None:
            by_module.setdefault(prog.module, []).append(prog)
    read: Dict[Tuple[int, int], Any] = {}

    def scopes_for(module: str, names: Any) -> Tuple[Any, Any]:
        """(scopes, mixed) of the compiled shape of ``module`` whose
        instructions name most of ``names``: the newest first, and no
        further than one that names them all."""
        best: Tuple[Any, Any] = ({}, set())
        for prog in by_module.get(module, []):
            for i in reversed(range(len(prog.built()))):
                if (id(prog), i) not in read:
                    text = prog.hlo_text(i)
                    read[(id(prog), i)] = None if text is None \
                        else tracing.hlo_scopes(text)
                found = read[(id(prog), i)]
                if found is not None and (not best[0] or len(
                        names & found[0].keys()) > len(
                        names & best[0].keys())):
                    best = found
                if best[0] and names <= best[0].keys():
                    return best
        return best

    def instruction(text: str) -> Tuple[str, str]:
        """(name, opcode) of an "XLA Ops" event, which is named by the
        instruction's whole text (or, in a test, by its name alone)."""
        name, _, rest = text.partition(" = ")
        return name.lstrip("%"), tracing._opcode(rest) if rest else ""

    n = max(len(planes), 1)
    lo, hi = window or (float("-inf"), float("inf"))
    out: Dict[str, Any] = {"devices": len(planes), "ops_s": 0.0,
                           "mixed_s": 0.0, "by_role": {},
                           "by_component": {}, "by_program": {}}

    def add(table: Dict[str, float], key: str, secs: float) -> None:
        table[key] = table.get(key, 0.0) + secs

    def program_row(module: str) -> Dict[str, Any]:
        role = by_module[module][-1].role if module in by_module \
            else "unregistered"
        return out["by_program"].setdefault(module, {
            "role": role, "runs": 0.0, "seconds": 0.0, "by_component": {}})

    for lines in planes.values():
        modules = sorted((s, s + d, name)
                         for name, s, d in lines.get("XLA Modules", []))
        starts = [m[0] for m in modules]
        runs: Dict[str, List[Tuple[str, float]]] = {}
        for text, s, d in lines.get("XLA Ops", []):
            secs = (min(s + d, hi) - max(s, lo)) / 1e9
            name, opcode = instruction(text)
            if secs <= 0 or opcode in tracing._CONTROL:
                continue
            i = bisect.bisect_right(starts, s) - 1
            run = modules[i][2] if i >= 0 and s < modules[i][1] else "-"
            runs.setdefault(run, []).append((name, secs / n))
        for run, events in runs.items():
            module = run.split("(", 1)[0]
            scopes, mixed = scopes_for(module, {
                name for name, _ in events
                if re.sub(r"\.\d+$", "", name) not in tracing._CONTROL})
            row = program_row(module)
            role = row["role"]
            for name, secs in events:
                # a while's name is a leaf's only by accident: the
                # program's own text says which names are leaves
                if scopes and name not in scopes and \
                        re.sub(r"\.\d+$", "", name) in tracing._CONTROL:
                    continue
                comp, part, direction = scopes.get(
                    name, (tracing.UNSCOPED, "", "fwd"))
                key = f"{comp}/{part} {direction}" if part \
                    else f"{comp} {direction}"
                out["ops_s"] += secs
                row["seconds"] += secs
                add(row["by_component"], key, secs)
                add(out["by_component"], key, secs)
                add(out["by_role"], role, secs)
                if name in mixed:
                    out["mixed_s"] += secs
        for s, e, name in modules:
            if e > lo and s < hi:
                program_row(name.split("(", 1)[0])["runs"] += 1.0 / n
    out["mixed_pct"] = 100.0 * out["mixed_s"] / out["ops_s"] \
        if out["ops_s"] else 0.0
    return out


if getenv("MXNET_PROFILER_AUTOSTART", 0):
    set_state("run")
