"""From a profiler trace (.xplane.pb) to device busy time, per-program
and per-operation device time and idle gaps.

The reduction works on a plain structure, so that it can be checked on
synthetic planes without a chip::

    planes = {"/device:TPU:0": {"XLA Ops": [(name, start_ns, dur_ns), ...],
                                "XLA Modules": [...]}, ...}

What a v5e trace looks like (looked at by hand, PR 24): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds every HLO
operation that ran, each event named by the instruction's whole text
(``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...``), which
``op_name`` cuts to ``fusion.12``, keeping a custom call's target as
``custom-call.3[tpu_custom_call]``; its line ``XLA Modules`` holds one
event per run of a jitted program, named ``jit_<function>(<fingerprint>)``.
Operations may overlap, so busy time is a union, never a sum.  Host
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` shows there under its own name, on the
same clock as the device lines.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "chipbench.window"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text):
    """An "XLA Ops" event's name, cut from the instruction's text to
    the instruction's own name, with a custom call's target kept."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if " custom-call(" in text:
        target = _TARGET.search(text)
        if target:
            name += f"[{target.group(1)}]"
    return name


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path):
    """(planes, marker): the device planes' ops and modules lines in the
    plain structure above, and the (start_ns, end_ns) of the host's
    MARKER annotation, or None where there is none."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, marker = {}, None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines[line.name] = [
                        (op_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)) for ev in line.events]
                elif line.name == MODULES_LINE:
                    lines[line.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
            planes[plane.name] = lines
        elif plane.name == HOST_PLANE and marker is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                        break
                if marker is not None:
                    break
    return planes, marker


def union(intervals, lo, hi):
    """Merged, sorted intervals of ``intervals`` [(start, end)] clipped
    to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _clipped(events, lo, hi):
    """(name, seconds inside [lo, hi]) of each event that reaches into
    the window."""
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, (e - s) / 1e9


def program_name(module_event_name):
    """``jit__step(1234567)`` -> ``jit__step``."""
    return module_event_name.split("(", 1)[0]


def reduce(planes, window):
    """Reduce device planes over ``window`` (start_ns, end_ns).

    Returns a dict: ``window_s``; ``busy_s``, the MEAN over devices of
    each device's union of operation intervals (never the sum);
    ``busy_by_device``; ``ops`` and ``programs``, {name: [seconds,
    count]} meaned over devices; ``gaps``, the idle intervals
    (start_ns, end_ns) of the lowest-numbered device, longest first.
    """
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty trace window {window}")
    devices = sorted(planes, key=lambda n: int(DEVICE_PLANE.match(n)[1]))
    if not devices:
        raise ValueError("the trace holds no device plane")
    n = len(devices)
    busy_by_device, ops, programs, gaps = {}, {}, {}, []

    def add(table, name, secs):
        row = table.setdefault(name, [0.0, 0.0])
        row[0] += secs / n
        row[1] += 1.0 / n

    for i, dev in enumerate(devices):
        op_events = planes[dev].get(OPS_LINE, [])
        busy = union(((s, s + d) for _, s, d in op_events), lo, hi)
        busy_by_device[dev] = _length(busy) / 1e9
        for name, secs in _clipped(op_events, lo, hi):
            add(ops, name, secs)
        for name, secs in _clipped(planes[dev].get(MODULES_LINE, []), lo, hi):
            add(programs, program_name(name), secs)
        if i == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            gaps = sorted(((edges[j], edges[j + 1])
                           for j in range(0, len(edges), 2)
                           if edges[j + 1] > edges[j]),
                          key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_device.values()) / n,
        "busy_by_device": busy_by_device,
        "ops": ops,
        "programs": programs,
        "gaps": gaps,
    }


def idle_pct(reduction):
    """Share of the window in which no operation ran on the device."""
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])


def top(table, k=10):
    """The k kinds of operation in {name: [seconds, count]} with most
    seconds, as [[kind, seconds], ...]: ``fusion.12`` and ``fusion.13``
    are both of the kind ``fusion``, and the kind carries how many
    distinct operations it sums."""
    kinds = {}
    for name, (secs, _) in table.items():
        row = kinds.setdefault(re.sub(r"\.\d+(?=$|\[)", "", name), [0.0, 0])
        row[0] += secs
        row[1] += 1
    rows = sorted(kinds.items(), key=lambda kv: -kv[1][0])[:k]
    return [[f"{kind} ({n} ops)", secs] for kind, (secs, n) in rows]


def gaps_by_phase(gaps, phases, offset_ns, k=10):
    """The k longest idle gaps, each named by the phase the benchmark
    was in at the gap's middle.  ``phases`` is [(host_ns, name)] in
    time order, each phase lasting to the next entry; ``offset_ns`` is
    trace clock minus host clock."""
    rows = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2 - offset_ns
        name = "unknown"
        for t, phase in phases:
            if t > mid:
                break
            name = phase
        rows.append([name, (e - s) / 1e9])
    return rows


class TraceWindow:
    """Trace a short sub-window of a run::

        with TraceWindow() as tw:
            ...                       # a few steps, a few seconds
        reduction = tw.reduction()    # reduce() plus "offset_ns"

    The window is the span of a MARKER annotation the main thread holds
    open, read back from the trace itself, so it is on the device
    lines' clock.  ``offset_ns`` is trace clock minus
    ``time.perf_counter_ns()``.  The trace is written under TMPDIR and
    removed once reduced.
    """

    def __enter__(self):
        import tempfile
        import time
        import jax
        self._dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        jax.profiler.start_trace(self._dir.name)
        self._marker = jax.profiler.TraceAnnotation(MARKER)
        self._marker.__enter__()
        self._host_start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        import jax
        self._marker.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduction(self):
        try:
            planes, marker = read_xplane(newest_xplane(self._dir.name))
        finally:
            self._dir.cleanup()
        if marker is None:
            raise ValueError(f"the trace holds no {MARKER!r} annotation")
        out = reduce(planes, marker)
        out["offset_ns"] = marker[0] - self._host_start_ns
        return out
