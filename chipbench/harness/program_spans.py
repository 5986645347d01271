"""The program's own spans (``mxnet_tpu.tracing``), as the per-layer
readers use them.

A record of the program's ring is a dict with ``name``, ``t_begin`` and
``t_end`` in ``time.perf_counter()`` seconds, ``tid`` (the thread) and
``seq`` (the order of finishing).  Every function here works on plain
lists of such dicts, so chipbench/tests checks them on synthetic
records; only ``resident`` touches the program.

A traced run's idle gaps are on the trace's clock, in nanoseconds;
``offset_ns`` (``TraceWindow``) is trace clock minus
``perf_counter_ns``, so a gap's host time is ``(ns - offset_ns) / 1e9``.

A program without these spans (an older commit) has nothing resident:
every reader then returns None and the line leaves its metric out.
"""
import bisect
import json
import sys

from chipbench.harness import trace_reduce

OUTSIDE = "outside"
# recorded after the fact (tracing.record_span), by the thread that saw
# the wait end: an interval, not a span open on that thread
RETROACTIVE = ("queue.wait",)


def _say(text):
    sys.stderr.write(f"chipbench: spans: {text}\n")


def window(ctx):
    """The measured window on the host clock, (lo, hi) in seconds."""
    t0 = ctx["t_proc"] + ctx["end_to_end"]["setup_s"]
    return t0, t0 + ctx["seconds"]


def resident(name, lo, hi, by="t_begin"):
    """The ring's records named ``name`` (every name where it is None)
    whose ``by`` stamp lies inside [lo, hi], oldest first.  Says on
    stderr how many it found and, where the ring has wrapped past
    ``lo``, from when on it could see."""
    from mxnet_tpu import tracing
    records = tracing.spans()
    found = [r for r in records if lo <= r[by] <= hi
             and (name is None or r["name"] == name)]
    note = ""
    if records and records[0]["seq"] > 0 and records[0]["t_end"] > lo:
        note = (f"; the ring was overwritten up to "
                f"{records[0]['t_end'] - lo:.3f} s after the start")
    _say(f"{len(found)} x {name or 'any name'} with {by} in "
         f"[{lo:.3f}, {hi:.3f}] ({len(records)} resident{note})")
    return found


def duration(span):
    return span["t_end"] - span["t_begin"]


def mean_ms(ctx, name):
    """Mean milliseconds of the ``name`` spans that began inside the
    measured window; None where there is none."""
    found = resident(name, *window(ctx))
    if not found:
        return None
    return 1e3 * sum(map(duration, found)) / len(found)


def on_thread_of(spans, root):
    """The spans open on the thread that recorded ``root`` spans (the
    engine's owner thread, the trainer's), sorted by their beginning,
    a parent before its children."""
    tid = next((s["tid"] for s in spans if s["name"] == root), None)
    return sorted((s for s in spans if s["tid"] == tid
                   and s["name"] not in RETROACTIVE),
                  key=lambda s: (s["t_begin"], -s["t_end"]))


def self_time(parent, spans):
    """``parent``'s duration minus the union of the spans of its thread
    that lie inside it — by time, not by ``parent_id``: engine.prefill
    sits in the request's trace and still runs inside the iteration.
    ``spans`` is sorted by ``t_begin`` (``on_thread_of``)."""
    lo, hi = parent["t_begin"], parent["t_end"]
    i = bisect.bisect_left(spans, lo, key=lambda s: s["t_begin"])
    inside = []
    for s in spans[i:]:
        if s["t_begin"] > hi:
            break
        if s is not parent and s["tid"] == parent["tid"] \
                and s["t_end"] <= hi:
            inside.append((s["t_begin"], s["t_end"]))
    covered = sum(e - s for s, e in trace_reduce.union(inside, lo, hi))
    return (hi - lo) - covered


def innermost(spans):
    """One thread's spans flattened to [(begin, end, name)], sorted and
    disjoint: at each moment the innermost span open.  ``spans`` is
    sorted as ``on_thread_of`` sorts; a span that outlasts its parent
    is cut at the parent's end."""
    out, stack, cursor = [], [], None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for s in spans:
        begin, end = s["t_begin"], s["t_end"]
        close_until(begin)
        if stack:
            end = min(end, stack[-1][0])
            if begin > cursor:
                out.append((cursor, begin, stack[-1][1]))
        cursor = begin
        stack.append((end, s["name"]))
    close_until(float("inf"))
    return out


def idle_by_span(gaps, offset_ns, spans):
    """{span name: seconds} of the device's idle ``gaps`` [(start_ns,
    end_ns)] on the trace's clock, each moment given to the innermost
    of ``spans`` (one thread's, ``on_thread_of``) open then, and to
    ``"outside"`` where none is.  Every name of ``spans`` is in the
    table, at 0.0 where no gap met it."""
    segments = innermost(spans)
    starts = [a for a, _, _ in segments]
    table = dict.fromkeys([OUTSIDE] + [s["name"] for s in spans], 0.0)
    for start_ns, end_ns in gaps:
        lo, hi = (start_ns - offset_ns) / 1e9, (end_ns - offset_ns) / 1e9
        left = hi - lo
        for a, b, name in segments[max(0, bisect.bisect_right(starts, lo)
                                       - 1):]:
            if a >= hi:
                break
            shared = min(b, hi) - max(a, lo)
            if shared > 0:
                table[name] += shared
                left -= shared
        table[OUTSIDE] += max(0.0, left)
    return table


def idle_table(ctx, root):
    """``idle_by_span`` of the traced run's gaps over the thread that
    recorded ``root`` spans; computed once a run (kept in ``ctx``, which
    the run's readers share) and written to stderr as ``idle_by_span``.
    None without a trace or without a resident ``root`` span."""
    red = ctx["reduction"]
    if red is None:
        return None
    if "idle_by_span" not in ctx:
        spans = on_thread_of(
            resident(None, float("-inf"), float("inf")), root)
        ctx["idle_by_span"] = idle_by_span(
            red["gaps"], red["offset_ns"], spans) if spans else None
        sys.stderr.write("chipbench: " + json.dumps(
            {"idle_by_span": ctx["idle_by_span"],
             "window_s": red["window_s"]}) + "\n")
    return ctx["idle_by_span"]


# Which share of the serving cell's idle time each group of span names
# takes; what is in neither (engine.iteration's own time, engine.emit,
# "outside") is the engine's host time, so the three partition the idle
# share whatever names a later program adds.
IDLE_GROUPS = {
    "decode_call": ("model.step", "model.step.dispatch",
                    "model.step.readback", "model.verify", "engine.draft",
                    "engine.verify"),
    "admission": ("engine.prefill", "model.prefill", "kv.write_prompt",
                  "model.select"),
}


def idle_pct(ctx, group):
    """100 x the idle seconds of ``group`` ("decode_call", "admission"
    or "engine_host") over the traced window.  None unless the engine's
    thread recorded a model.step or model.verify: a program without
    them cannot split its iteration."""
    table = idle_table(ctx, "engine.iteration")
    if table is None or not any(
            n in table for n in ("model.step.readback", "model.verify")):
        return None
    named = {g: sum(table.get(n, 0.0) for n in names)
             for g, names in IDLE_GROUPS.items()}
    named["engine_host"] = sum(table.values()) - sum(named.values())
    return 100.0 * named[group] / ctx["reduction"]["window_s"]
