"""CPU rehearsals of chipbench — run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q -p no:cacheprovider

They check control flow, file lookup, the last line's validator, the
trace reduction and the traffic generator.  No number they see is a
statement about speed.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402
from chipbench.harness import (flops, lastline, trace_reduce,  # noqa: E402
                               traffic)

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


# ---------------------------------------------------------------------------
# the last line
# ---------------------------------------------------------------------------

UNITS = {0: {"setup_s": "s", "serve_tokens_per_s": "tokens/s",
             "ttft_p95_ms": "ms"},
         1: {"warmup_s": "s", "kv_migrations": "count",
             "device_idle_pct.serve": "%"}}


def good_line(trace, chips=1):
    units = UNITS[trace]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
              "memory_peak_bytes": 9_000_000_000}
    if trace:
        device.update(busy_s=2.5, window_s=4.0)
    bd = {"device_ops": [["fusion.1", 1.5]],
          "idle_gaps": [["engine_loop", 0.01]]} if trace else None
    return lastline.build(True, 200, 0, {n: 1.5 for n in units}, units,
                          device, bd), units


@pytest.mark.parametrize("trace,chips", [(0, 1), (1, 1), (1, 4)])
def test_validator_accepts_a_good_line(trace, chips):
    line, units = good_line(trace, chips)
    text = lastline.validate(line, units, chips, trace)
    assert json.loads(text)["device"]["count"] == chips


def _break(line, path, value):
    line = copy.deepcopy(line)
    node = line
    for key in path[:-1]:
        node = node[key]
    if value is KeyError:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return line


@pytest.mark.parametrize("trace,path,value", [
    (0, ["correct"], "yes"),
    (0, ["attempted"], 0),
    (0, ["failed"], 201),
    (0, ["failed"], KeyError),
    (0, ["metrics", "ttft_p95_ms"], KeyError),          # end-to-end: all
    (0, ["metrics", "ttft_p95_ms", "value"], float("nan")),
    (0, ["metrics", "ttft_p95_ms", "value"], 0.0),
    (0, ["metrics", "ttft_p95_ms", "unit"], "s"),
    (0, ["metrics", "ttft_p95_ms"], 212.4),
    (0, ["metrics", "surprise"], {"value": 1.0, "unit": "ms"}),
    (0, ["device", "platform"], "cpu"),
    (0, ["device", "count"], 4),
    (0, ["device", "memory_peak_bytes"], 0),
    (1, ["device", "busy_s"], KeyError),
    (1, ["device", "busy_s"], 0.0),
    (1, ["device", "busy_s"], 4.5),                     # > window_s
    (1, ["device", "window_s"], None),
    (1, ["metrics"], {}),
    (1, ["breakdown", "device_ops"], [["x", 1.0]] * 11),
    (1, ["breakdown", "idle_gaps"], [["x"]]),
    (1, ["breakdown", "other"], []),
])
def test_validator_rejects_a_bad_line(trace, path, value):
    line, units = good_line(trace)
    with pytest.raises(lastline.LastLineError):
        lastline.validate(_break(line, path, value), units, 1, trace)


def test_traced_line_may_leave_out_a_metric_that_found_nothing():
    line, units = good_line(1)
    del line["metrics"]["kv_migrations"]
    lastline.validate(line, units, 1, 1)


def test_share_of_a_peak_over_105_is_refused():
    units = {"mfu_pct": "%", "flash_roofline_pct": "%"}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 1.0, "window_s": 1.0}
    for name in ("mfu_pct", "flash_roofline_pct"):
        line = lastline.build(True, 1, 0, {name: 106.0}, units, device)
        with pytest.raises(lastline.LastLineError):
            lastline.validate(line, units, 1, 1)


def test_emit_prints_nothing_and_exits_nonzero_on_a_bad_line(capsys):
    line, units = good_line(0)
    line["device"]["platform"] = "cpu"
    with pytest.raises(SystemExit) as e:
        lastline.emit(line, units, 1, 0)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# the trace reduction, on synthetic planes
# ---------------------------------------------------------------------------

def _planes(n_devices):
    ms = 1_000_000
    planes = {}
    for d in range(n_devices):
        shift = d * ms          # device d runs 1 ms later
        planes[f"/device:TPU:{d}"] = {
            trace_reduce.OPS_LINE: [
                ("before", 0, 5 * ms),                  # out of window
                ("fusion.1", 8 * ms + shift, 4 * ms),   # clipped at 10
                ("fusion.2", 20 * ms + shift, 10 * ms),
                ("nested", 22 * ms + shift, 2 * ms),    # overlaps fusion.2
                ("custom-call.9[tpu_custom_call]", 40 * ms + shift, 5 * ms),
                ("after", 95 * ms, 20 * ms),            # clipped at 100
            ],
            trace_reduce.MODULES_LINE: [
                ("jit__step(123)", 20 * ms + shift, 10 * ms),
                ("jit__step(123)", 40 * ms + shift, 5 * ms),
                ("jit__prefill(9)", 95 * ms, 20 * ms),
            ],
        }
    return planes, (10 * ms, 100 * ms)


@pytest.mark.parametrize("n", [1, 4])
def test_reduce_unions_clips_and_means_over_devices(n):
    planes, window = _planes(n)
    red = trace_reduce.reduce(planes, window)
    assert red["window_s"] == pytest.approx(0.090)
    # device d: fusion.1 clipped to (2 + d) ms for d <= 2 and 4 ms for
    # d = 3, + 10 + 5 + 5; "nested" adds nothing
    per_dev = [(min(4, 2 + d) + 20) / 1e3 for d in range(n)]
    assert list(red["busy_by_device"].values()) == pytest.approx(per_dev)
    assert red["busy_s"] == pytest.approx(sum(per_dev) / n)    # not the sum
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["ops"]["custom-call.9[tpu_custom_call]"] == pytest.approx(
        [0.005, 1.0])
    assert "before" not in red["ops"]
    assert red["programs"]["jit__step"] == pytest.approx([0.015, 2.0])
    assert red["programs"]["jit__prefill"] == pytest.approx([0.005, 1.0])
    # gaps of device 0, longest first: 45-95, 30-40, 12-20
    assert [round((e - s) / 1e6) for s, e in red["gaps"]] == [50, 10, 8]
    # fusion.1 and fusion.2 are one kind
    assert trace_reduce.top(red["ops"], 2)[0] == [
        "fusion (2 ops)", pytest.approx(sum(per_dev) / n - 0.010)]


def test_gaps_are_named_by_the_phase_at_their_middle():
    planes, window = _planes(1)
    red = trace_reduce.reduce(planes, window)
    offset = 1_000
    phases = [(0 - offset, "dispatch"), (35_000_000 - offset, "loss_fetch"),
              (60_000_000 - offset, "waiting_for_request")]
    rows = trace_reduce.gaps_by_phase(red["gaps"], phases, offset)
    assert rows == [["waiting_for_request", 0.05], ["loss_fetch", 0.01],
                    ["dispatch", 0.008]]


def test_op_name_cuts_the_instruction_text_and_keeps_a_custom_call_target():
    assert trace_reduce.op_name(
        '%custom-call.17 = bf16[16,512,16,64]{3,2,1,0} custom-call(bf16[2] '
        '%x), custom_call_target="tpu_custom_call", backend_config="..."'
    ) == "custom-call.17[tpu_custom_call]"
    # an operand that is a custom call does not make the user one
    assert trace_reduce.op_name(
        "%fusion.1355 = (bf16[30522,1024]{1,0}) fusion(bf16[30522,1024] "
        "%custom-call.17, f32[] %c), kind=kLoop, calls=%fused_computation.8"
    ) == "fusion.1355"
    assert trace_reduce.op_name("copy.3") == "copy.3"


def test_reduce_refuses_a_trace_without_devices():
    with pytest.raises(ValueError):
        trace_reduce.reduce({}, (0, 10))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

MIX = {"rate_per_s": 5.0, "ramp_s": 5.0,
       "prompt": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
       "output": {"median": 96, "sigma": 0.6, "min": 16, "max": 256}}


def test_schedule_repeats_for_a_seed_and_differs_across_seeds():
    big = 3_000_000_001             # more than 32 signed bits hold
    a, b, c = (traffic.schedule(MIX, 20, s, 50257) for s in (big, big, 7))
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [r.due_s for r in a] != [r.due_s for r in c]
    # the same work in another order: lengths and gaps are the mix's
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    assert a[0].due_s >= -MIX["ramp_s"] and a[-1].due_s < 21
    p, o = MIX["prompt"], MIX["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(o["min"] <= r.max_new <= o["max"] for r in a)
    assert len(a) == round(MIX["rate_per_s"] * (MIX["ramp_s"] + 20))


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert traffic.percentile(xs, 0.95) == 96
    assert traffic.percentile(xs, 0.5) == 51
    assert traffic.percentile([3.0], 0.95) == 3.0
    assert traffic.percentile([], 0.95) is None


def test_open_loop_sends_on_schedule_and_stamps_tokens():
    class Stream(list):
        def cancel(self):
            pass
    reqs = traffic.schedule(dict(MIX, rate_per_s=50.0, ramp_s=0.1), 0.4,
                            1, 100)
    loop = traffic.OpenLoop(lambda p, n: Stream(range(n)), reqs)
    loop.start(time.perf_counter() + 0.15)
    time.sleep(0.6)
    loop.stop_sending()
    assert loop.join(5.0)
    assert all(r.finished and r.sent_s >= r.due_s for r in reqs)
    assert max(r.sent_s - r.due_s for r in reqs) < 0.1


# ---------------------------------------------------------------------------
# lookup by name, and each job end to end at a tiny size
# ---------------------------------------------------------------------------

TINY_ARCH = {"layers": 2, "width": 64, "heads": 4, "ffn": 128, "vocab": 503,
             "causal": False, "pre_ln": False, "gelu_approx": False,
             "layer_norm_eps": 1e-12}
END_TO_END = {"setup_s": "s", "train_tokens_per_s_chip": "tokens/s/chip",
              "serve_tokens_per_s": "tokens/s", "ttft_p95_ms": "ms",
              "itl_p95_ms": "ms"}
TINY = {
    "tiny_bert.train": (
        ["setup_s", "train_tokens_per_s_chip", "warmup_s",
         "step_dispatch_ms", "mfu_pct", "flash_ms_per_step",
         "flash_roofline_pct", "device_idle_pct.train"],
        {"arch": dict(TINY_ARCH, positions=64),
         "zoo": "mxnet_tpu.gluon.model_zoo.bert:BERTModel", "zoo_args": [],
         "zoo_kwargs": {"vocab_size": 503, "num_layers": 2, "units": 64,
                        "hidden_size": 128, "num_heads": 4,
                        "max_length": 64, "dropout": 0.0,
                        "use_pooler": False, "use_decoder": True,
                        "use_classifier": False},
         "train_dtype": "float32"},
        {"job": "train_mlm", "batch": 4, "seq_len": 32, "masked": 5,
         "mesh": {"dp": 1}, "rules": "DATA_PARALLEL_RULES",
         "optimizer": "adamw", "optimizer_params": {"learning_rate": 1e-4},
         "warmup_steps": 2, "check_sequences": 2, "trace_steps": 3}),
    "tiny_gpt.serve": (
        ["setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
         "warmup_s", "decode_batch_mean", "kv_migrations", "decode_step_ms",
         "prefill_ms", "decode_hbm_pct", "device_idle_pct.serve",
         "generator_lag_p95_ms"],
        {"arch": dict(TINY_ARCH, positions=256, causal=True, pre_ln=True,
                      gelu_approx=True, layer_norm_eps=1e-5),
         "zoo": "mxnet_tpu.gluon.model_zoo.gpt:GPTModel", "zoo_args": [],
         "zoo_kwargs": {"vocab_size": 503, "num_layers": 2, "units": 64,
                        "hidden_size": 128, "num_heads": 4,
                        "max_length": 256, "dropout": 0.0},
         "serve_dtype": "float32"},
        {"job": "serve_generate",
         "engine": {"max_slots": 4, "kv_buckets": [64, 128, 256],
                    "prefix_slots": 0, "queue_limit": 1000,
                    "max_tokens": 32},
         "traffic": {"rate_per_s": 20.0, "ramp_s": 0.5,
                     "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                                "max": 64},
                     "output": {"median": 8, "sigma": 0.5, "min": 4,
                                "max": 16},
                     "at_window_end": "drain", "drain_s": 20.0},
         "check": {"prompt_lengths": [20, 40], "new_tokens": 8,
                   "reference_length": 64},
         "trace_at_s": 0.2, "trace_window_s": 0.5}),
}


def tiny_root(tmp_path, cell_name):
    """A root that holds nothing of the real benchmark's data: one cell,
    one configuration, and the job and metric files under a directory
    of another name."""
    metrics, config, cell = TINY[cell_name]
    root = tmp_path / "root"
    for sub in ("workloads", "configs"):
        os.makedirs(root / "elsewhere" / sub)
    for sub in ("jobs", "metrics"):
        shutil.copytree(os.path.join(ROOT, "chipbench", sub),
                        root / "elsewhere" / sub)
    config_name = cell_name.split(".")[0]
    bench = {
        "paths": ["elsewhere"],
        "configs": [{"name": config_name, "source": "none",
                     "file": f"elsewhere/configs/{config_name}.json",
                     "reduced": [], "why": "tiny"}],
        "workloads": [{"name": cell_name, "config": config_name,
                       "traffic": cell_name.split(".")[1], "chips": 1,
                       "why": "tiny"}],
        "end_to_end": [{"name": m, "unit": END_TO_END[m]}
                       for m in metrics if m in END_TO_END],
        "per_layer": [],
    }
    for m in metrics:
        if m not in END_TO_END:     # the metric file says what it is
            mod = run._load_module(os.path.join(ROOT, "chipbench",
                                                "metrics", m + ".py"))
            bench["per_layer"].append(
                {"name": m, "unit": mod.UNIT, "layer": mod.LAYER,
                 "moves": mod.MOVES, "source": mod.SOURCE})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "elsewhere" / "configs" / f"{config_name}.json").write_text(
        json.dumps(config))
    (root / "elsewhere" / "workloads" / f"{cell_name}.json").write_text(
        json.dumps(dict(cell, config=config_name, chips=1)))
    return str(root), bench


def test_run_finds_files_that_exist_only_under_a_temporary_directory(
        tmp_path):
    root, bench = tiny_root(tmp_path, "tiny_bert.train")
    metric = os.path.join(root, "elsewhere", "metrics", "new_metric.py")
    with open(metric, "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bench["per_layer"].append(
        {"name": "new_metric", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "new",
         "moves": "train_tokens_per_s_chip",
         "workloads": ["tiny_bert.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    found = run.resolve(root, "tiny_bert.train")
    assert found["cell"]["batch"] == 4
    assert found["config"]["arch"]["width"] == 64
    assert found["job"].__file__.startswith(root)
    assert found["readers"]["new_metric"].read({}) == 42.0
    assert "decode_step_ms" not in found["readers"]
    with pytest.raises(run.BenchError):
        run.resolve(root, "no_such.cell")


def test_cell_metrics_follow_the_workloads_key():
    bench = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "a", "unit": "ms", "workloads": ["x"]}],
             "per_layer": [{"name": "b", "unit": "%", "workloads": ["y"]}]}
    assert lastline.cell_metrics(bench, "x", 0) == {"setup_s": "s",
                                                    "a": "ms"}
    assert lastline.cell_metrics(bench, "y", 0) == {"setup_s": "s"}
    assert lastline.cell_metrics(bench, "y", 1) == {"b": "%"}


def test_each_metric_file_says_what_benchmark_json_says():
    for m in BENCH["per_layer"]:
        mod = run._load_module(os.path.join(ROOT, "chipbench", "metrics",
                                            m["name"] + ".py"))
        assert (mod.LAYER, mod.MOVES, mod.UNIT, mod.SOURCE) == (
            m["layer"], m["moves"], m["unit"], m["source"]), m["name"]


def _synthetic_devices(monkeypatch):
    """On the CPU the profiler records no device plane: keep the real
    trace's marker and put synthetic device events inside it."""
    real = trace_reduce.read_xplane

    def fake(path):
        _, marker = real(path)
        lo, hi = marker
        q = (hi - lo) // 4
        return {"/device:TPU:0": {
            trace_reduce.OPS_LINE: [("custom-call.1[tpu_custom_call]", lo + q, q),
                                    ("fusion.7", lo + 2 * q, q)],
            trace_reduce.MODULES_LINE: [("jit__step(1)", lo + q, 2 * q)],
        }}, marker
    monkeypatch.setattr(trace_reduce, "read_xplane", fake)
    # "cpu" is rightly not in the table of peaks
    v5e = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: v5e)


@pytest.mark.parametrize("cell_name", sorted(TINY))
@pytest.mark.parametrize("trace", [0, 1])
def test_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch, cell_name,
                                       trace):
    import jax
    root, bench = tiny_root(tmp_path, cell_name)
    if trace:
        _synthetic_devices(monkeypatch)
    found = run.resolve(root, cell_name)
    line, units = run.measure(found, cell_name, 3_000_000_001, 1.5, trace,
                              jax.devices()[:1], time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert units == lastline.cell_metrics(bench, cell_name, trace)
    assert set(line["metrics"]) == set(units)
    # a CPU line must not pass for a chip's: only the device is wrong
    with pytest.raises(lastline.LastLineError, match="platform"):
        lastline.validate(line, units, 1, trace)
    line["device"].update(platform="tpu", memory_peak_bytes=1)
    lastline.validate(line, units, 1, trace)
    if trace:
        assert {row[0] for row in line["breakdown"]["device_ops"]} == {
            "custom-call[tpu_custom_call] (1 ops)", "fusion (1 ops)"}
        assert line["device"]["busy_s"] == pytest.approx(
            line["device"]["window_s"] / 2, rel=0.01)


def test_the_command_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "bert_large.train_mlm512", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "cpu" in proc.stderr
