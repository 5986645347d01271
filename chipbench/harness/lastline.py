"""The benchmark's last line: ``build()`` makes it, ``validate()`` holds
it to the contract, ``emit()`` prints it or exits non-zero.

Nothing else in chipbench writes to stdout after ``emit()``; a line
that would break the contract is never printed (PR 22 was lost to one).
"""
import json
import math
import re
import sys

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")


class LastLineError(Exception):
    """The line would break the contract; the message says where."""


def cell_metrics(bench, workload, trace):
    """{name: unit} of the metrics ``workload`` reports for this
    ``--trace`` value: its end-to-end metrics with 0, its per-layer
    metrics with 1.  A metric without a ``workloads`` key is in every
    cell."""
    return {m["name"]: m["unit"]
            for m in bench["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def build(correct, attempted, failed, values, units, device,
          breakdown=None):
    """The line as a dict.  ``values`` is {metric: number}; a metric
    whose reader found nothing (None) is left out."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(v), "unit": units[name]}
                    for name, v in values.items() if v is not None},
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def _number(x):
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(line, expected, chips, trace):
    """Raise LastLineError unless ``line`` meets the contract for a cell
    whose metrics (for this --trace value) are ``expected`` {name: unit}
    and that runs on ``chips`` chips.  End-to-end metrics (trace 0) must
    all be there; of the per-layer metrics (trace 1) at least one, and
    none that the cell does not list."""
    def need(cond, msg):
        if not cond:
            raise LastLineError(msg)

    need(isinstance(line, dict), "the line is not an object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        need(key in line, f"key {key!r} is missing")
    need(isinstance(line["correct"], bool), "correct is not a boolean")
    for key in ("attempted", "failed"):
        need(isinstance(line[key], int) and not isinstance(line[key], bool)
             and line[key] >= 0, f"{key} is not a count: {line[key]!r}")
    need(line["attempted"] > 0, "nothing was attempted")
    need(line["failed"] <= line["attempted"],
         f"failed {line['failed']} > attempted {line['attempted']}")

    metrics = line["metrics"]
    need(isinstance(metrics, dict) and metrics, "metrics is empty")
    unknown = sorted(set(metrics) - set(expected))
    need(not unknown, f"metrics the cell does not list: {unknown}")
    missing = sorted(set(expected) - set(metrics))
    if not trace:
        need(not missing, f"end-to-end metrics missing: {missing}")
    for name, m in metrics.items():
        need(NAME_RE.match(name) is not None, f"bad metric name {name!r}")
        need(isinstance(m, dict) and set(m) >= {"value", "unit"},
             f"{name}: needs value and unit, got {m!r}")
        need(_number(m["value"]), f"{name}: value {m['value']!r} is not "
                                  "a finite number")
        need(isinstance(m["unit"], str) and UNIT_RE.match(m["unit"])
             is not None, f"{name}: bad unit {m['unit']!r}")
        need(m["unit"] == expected[name],
             f"{name}: unit {m['unit']!r}, BENCHMARK.json says "
             f"{expected[name]!r}")
        if not trace:
            need(m["value"] > 0, f"{name}: end-to-end value "
                                 f"{m['value']!r} is not above 0")
        if "roofline" in name or "mfu" in name:
            need(m["value"] <= 105.0, f"{name}: {m['value']} % of a peak")

    dev = line["device"]
    need(isinstance(dev, dict), "device is not an object")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        need(key in dev, f"device.{key} is missing")
    need(dev["platform"] == "tpu", f"device.platform {dev['platform']!r}")
    need(isinstance(dev["kind"], str) and dev["kind"],
         "device.kind is not a string")
    need(dev["count"] == chips, f"device.count {dev['count']!r}, the cell "
                                f"asks for {chips}")
    need(isinstance(dev["memory_peak_bytes"], int)
         and dev["memory_peak_bytes"] > 0,
         f"device.memory_peak_bytes {dev['memory_peak_bytes']!r}")
    if trace:
        for key in ("busy_s", "window_s"):
            need(key in dev and _number(dev[key]),
                 f"device.{key} is missing or not a number")
        need(0 < dev["busy_s"] <= dev["window_s"],
             f"need 0 < busy_s <= window_s, got busy_s={dev['busy_s']} "
             f"window_s={dev['window_s']}")
    if "breakdown" in line:
        bd = line["breakdown"]
        need(isinstance(bd, dict) and set(bd) <= set(BREAKDOWN_KEYS),
             f"breakdown keys {sorted(bd) if isinstance(bd, dict) else bd}")
        for key, rows in bd.items():
            need(isinstance(rows, list) and len(rows) <= 10,
                 f"breakdown.{key}: more than 10 entries")
            for row in rows:
                need(isinstance(row, list) and len(row) == 2
                     and isinstance(row[0], str) and _number(row[1]),
                     f"breakdown.{key}: bad entry {row!r}")
    try:
        text = json.dumps(line, allow_nan=False)
    except (TypeError, ValueError) as e:
        raise LastLineError(f"not JSON: {e}") from None
    need("\n" not in text, "the line holds a newline")
    return text


def emit(line, expected, chips, trace, out=None):
    """Print the validated line as the last line of stdout, or exit
    non-zero with the reason on stderr and print nothing."""
    try:
        text = validate(line, expected, chips, trace)
    except LastLineError as e:
        sys.stderr.write(f"chipbench: refusing to print a last line that "
                         f"breaks the contract: {e}\n{line!r}\n")
        sys.exit(4)
    out = out or sys.stdout
    out.write(text + "\n")
    out.flush()
