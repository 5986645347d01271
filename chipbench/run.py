"""chipbench — the benchmark's one command.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the cell's chips: loads, warms up, checks the
outputs against the plain reference, measures for ``--seconds``, prints
ONE validated JSON line as the last line of stdout, exits.  It refuses
(non-zero, naming what jax found, no line) anything but a TPU with at
least the cell's chips.

Driven by data: the cell ``<name>`` of BENCHMARK.json is the file
``workloads/<name>.json`` under one of BENCHMARK.json's ``paths``; that
file names its job kind (``jobs/<job>.py``), its configuration is the
``file`` BENCHMARK.json gives for the cell's ``config``, and each
per-layer metric the cell reports is read by ``metrics/<metric>.py``.
Adding a cell, a configuration, a job kind or a metric is adding files
and entries; nothing here names one.
"""
import time

T_PROC = time.perf_counter()    # set-up counts from here

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.harness import flops, lastline   # noqa: E402


class BenchError(Exception):
    """The run cannot give a result; the message says why."""


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(path):
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(root, bench, *parts):
    """The file ``<path>/<parts...>`` under the first of the benchmark's
    ``paths`` that has it."""
    for p in bench["paths"]:
        path = os.path.join(root, p, *parts)
        if os.path.isfile(path):
            return path
    raise BenchError(f"no {os.path.join(*parts)} under any of "
                     f"{bench['paths']}")


def resolve(root, workload):
    """Everything that belongs to the cell ``workload``, found by name
    from ``root``/BENCHMARK.json."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise BenchError(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    cell = _load_json(_find(root, bench, "workloads", workload + ".json"))
    return {
        "bench": bench,
        "entry": entry,
        "chips": int(entry["chips"]),
        "cell": cell,
        "config": _load_json(os.path.join(root, config_entry["file"])),
        "job": _load_module(_find(root, bench, "jobs",
                                  cell["job"] + ".py")),
        "readers": {
            name: _load_module(_find(root, bench, "metrics", name + ".py"))
            for name in lastline.cell_metrics(bench, workload, trace=1)},
    }


def place_compile_cache():
    """JAX's persistent compilation cache goes to ``.jax_cache`` at the
    checkout's root — a fixed path inside the checkout (git-ignored),
    whatever the environment says — and without a size limit, so that
    only a cell's first run in a checkout compiles.  The chip tool's
    machine sets JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB; one run of
    bert_large.train_mlm512 writes more than that, so every run evicted
    what the next one needed and all 98 programs compiled again in each
    of five runs (my chip runs, PR 24).  Called before jax is imported:
    jax and ``mxnet_tpu.base.place_compile_cache`` take the directory
    from the environment."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def require_tpu(chips):
    """The first ``chips`` devices; exits non-zero, naming what jax
    found, unless they are TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} TPU device(s), but "
                 f"jax {jax.__version__} reports {len(devs)} device(s) of "
                 f"platform {sorted({d.platform for d in devs})} "
                 f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return devs[:chips]


def device_report(devices, trace):
    """The device as jax reports it.  The peak is the fullest chip's
    ``peak_bytes_in_use`` plus ``peak_bytes_reserved``: on this runtime
    the first counts live arrays only and the second the memory a
    compiled program sets aside for its temporaries (7.5 GB of the
    train cell's 11.9, my chip run, PR 24); the two are disjoint."""
    stats = [d.memory_stats() or {} for d in devices]
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                 + int(s.get("peak_bytes_reserved", 0))
                                 for s in stats),
    }
    if trace is not None:
        report["busy_s"] = trace["busy_s"]
        report["window_s"] = trace["window_s"]
    return report


def measure(found, workload, seed, seconds, trace, devices, t_proc):
    """Run the cell's job on ``devices``; returns its last line and the
    {metric: unit} the line is held to.  The internal entry point: the
    CPU rehearsals in chipbench/tests call it with CPU devices, the
    command only after require_tpu()."""
    ctx = {
        "workload": workload, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "devices": devices, "t_proc": t_proc,
        "cell": found["cell"], "config": found["config"],
        "chips": found["chips"],
    }
    result = found["job"].run(ctx)
    sys.stderr.write("chipbench: " + json.dumps(
        {"workload": workload, "seed": seed,
         "end_to_end": result["end_to_end"],
         "notes": result.get("notes", {}),
         "memory_stats": devices[0].memory_stats()}) + "\n")
    if result.get("compiled_in_window"):
        raise BenchError(f"{result['compiled_in_window']} program(s) "
                         "compiled or loaded inside the measured window")

    reduction = result.get("trace")
    units = lastline.cell_metrics(found["bench"], workload, trace)
    breakdown = None
    if trace:
        rctx = dict(ctx, end_to_end=result["end_to_end"],
                    readings=result["readings"], reduction=reduction,
                    peaks=flops.peaks(devices[0].device_kind))
        values = {}
        for name, reader in found["readers"].items():
            values[name] = reader.read(rctx)
            if values[name] is None:
                sys.stderr.write(f"chipbench: {name}: nothing to read, "
                                 "left out\n")
        breakdown = result.get("breakdown")
    else:
        values = {name: result["end_to_end"].get(name) for name in units}
    return lastline.build(result["correct"], result["attempted"],
                          result["failed"], values, units,
                          device_report(devices, reduction), breakdown), units


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    place_compile_cache()
    try:
        found = resolve(ROOT, args.workload)
        devices = require_tpu(found["chips"])
        line, units = measure(found, args.workload, args.seed, args.seconds,
                              args.trace, devices, T_PROC)
    except BenchError as e:
        sys.exit(f"chipbench: {e}")
    lastline.emit(line, units, found["chips"], args.trace)


if __name__ == "__main__":
    main()
