"""CPU rehearsal of the benchmark's newest job kind at a tiny size,
traced and untraced, as ``chipbench/tests/test_chipbench.py::
test_job_end_to_end_at_a_tiny_size`` rehearses the two older ones (that
file is run by hand; these count in tier-1).  They check control flow,
file lookup and the last line; no number they see is a statement about
speed."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402
from chipbench.harness import flops, lastline, trace_reduce  # noqa: E402

pytestmark = pytest.mark.host_mesh

# tiny_root() and the tiny configurations of the hand-run rehearsals
rehearsal = run._load_module(os.path.join(ROOT, "chipbench", "tests",
                                          "test_chipbench.py"))

CELL = "tiny_phi4.serve_reason"
TINY = (
    ["setup_s", "serve_tokens_per_s", "warmup_s", "decode_batch_mean",
     "kv_migrations", "decode_step_ms", "prefill_ms",
     "device_idle_pct.serve", "queue_wait_p95_ms", "admission_ms",
     "decode_dispatch_ms", "engine_host_ms", "idle_pct.decode_call",
     "idle_pct.admission", "idle_pct.engine_host", "cache_bytes_per_slot",
     "state_install_ms", "decode_hbm_pct", "decode_ahead_pct"],
    {"arch": {"vocab": 503, "width": 64, "kv_heads": 2, "head_dim": 16,
              "window": 8, "d_inner": 128, "d_state": 16, "d_conv": 4,
              "mamba_layers": 3, "window_layers": 2, "full_layers": 1,
              "cross_layers": 1},
     "zoo": "mxnet_tpu.gluon.model_zoo.phi4flash:get_phi4flash",
     "zoo_args": ["tiny"], "zoo_kwargs": {"dtype": "float32"},
     "serve_dtype": "float32"},
    {"job": "serve_state",
     "engine": {"max_slots": 4, "kv_buckets": [64, 128, 256],
                "prefix_slots": 0, "queue_limit": 1000, "max_tokens": 64},
     "traffic": {"rate_per_s": 20.0, "ramp_s": 0.5,
                 "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                 "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                 "at_window_end": "drain", "drain_s": 20.0},
     # forced: a slot that stays inside the window of 8, one that
     # crosses its edge, one that starts past it
     "check": {"prompt_lengths": [5, 40],
               "forced": {"prompts": [1, 3, 20], "copies": 1, "steps": 6,
                          "min_decisive": 3},
               "decode_prompt": 4, "new_tokens": 12,
               "batch_prompts": [8, 60]},
     "trace_at_s": 0.2, "trace_window_s": 0.5})


def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setitem(rehearsal.TINY, CELL, TINY)
    return rehearsal.tiny_root(tmp_path, CELL)


def synthetic_devices(monkeypatch):
    """On the CPU the profiler records no device plane: keep the real
    trace's marker and put synthetic device events inside it."""
    real = trace_reduce.read_xplane

    def fake(path):
        _, (lo, hi) = real(path)
        q = (hi - lo) // 8
        return {"/device:TPU:0": {
            trace_reduce.OPS_LINE: [("fusion.7", lo + 2 * q, 2 * q)],
            trace_reduce.MODULES_LINE: [("jit__step(1)", lo + q, 4 * q)],
        }}, (lo, hi)
    monkeypatch.setattr(trace_reduce, "read_xplane", fake)
    v5e = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: v5e)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch, trace):
    import jax
    root, bench = tiny_root(tmp_path, monkeypatch)
    if trace:
        synthetic_devices(monkeypatch)
    found = run.resolve(root, CELL)
    line, units = run.measure(found, CELL, 3_000_000_001, 1.5, trace,
                              jax.devices()[:1], time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert units == lastline.cell_metrics(bench, CELL, trace)
    assert set(line["metrics"]) == set(units)
    line["device"].update(platform="tpu", memory_peak_bytes=1)
    lastline.validate(line, units, 1, trace)
    if not trace:
        return
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert 0 < value["decode_hbm_pct"]
    assert value["state_install_ms"] > 0
    # outputs of 4-16 tokens: some steps are launched ahead, the ones
    # after a finish or an admission are not
    assert 0 < value["decode_ahead_pct"] < 100
    # rows at some bucket while the trace was open, 2 window layers,
    # 3 state layers
    per_slot = value["cache_bytes_per_slot"]
    fixed = 2 * 2 * 32 * 8 * 4 + 3 * 128 * (16 + 3) * 4
    assert (per_slot - fixed) / (2 * 32 * 4) in (64, 128, 256)


def _check_on_a_tiny_model(tmp_path, monkeypatch):
    found = run.resolve(tiny_root(tmp_path, monkeypatch)[0], CELL)
    job, config = found["job"], found["config"]
    return job, job.build_model(config, 7), found["cell"]["check"], \
        config["arch"]["vocab"]


def test_the_float8_control_is_refused_by_the_jobs_own_verdict(
        tmp_path, monkeypatch):
    """``chipbench/precision.py``'s second reading goes through the
    job's ``verdict`` and comes out as not correct by its limits; the
    reference against itself comes out correct."""
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import precision
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch)
    # 8 layers of width 64 gather less of a rounding than 32 of 2560:
    # float8_e4m3 reads 0.05-0.17 here (0.38-0.76 at full size on the
    # chip, PERF.md), so the tiny control rounds to float8_e5m2
    low = types.SimpleNamespace(cfg=model.cfg, params=dict(
        model.params, layers=jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e5m2) if a.ndim >= 2 else a,
            model.params["layers"])))
    n_min = spec["forced"]["min_decisive"]
    control = precision.control_readings(
        job, model, low, spec, np.random.default_rng(3), vocab)
    ok, refused = job.verdict(control, n_min)
    assert not ok and set(refused) & set(job.LIMITS)
    same = precision.control_readings(
        job, model, model, spec, np.random.default_rng(3), vocab)
    assert job.verdict(same, n_min) == (True, [])
    assert same["decisive_positions"] == control["decisive_positions"] >= n_min


@pytest.mark.parametrize("fault", ["ring_column", "slots_swapped",
                                   "too_few_decisive"])
def test_the_forced_decode_check_refuses_a_planted_fault(
        tmp_path, monkeypatch, fault):
    """The decode program driven directly: a ring written one column
    off, two slots' recurrent state exchanged, and a run with nothing
    decisive to compare each come out as not correct."""
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu import serving
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch)
    cell = {"check": dict(spec)}
    drive = job.drive_decode_program

    def faulty(model, engine, prompts, forced):
        answers, cache = drive(model, engine, prompts, forced)
        if fault == "ring_column":
            cache.state["wk"] = [jnp.roll(a, 1, axis=2)
                                 for a in cache.state["wk"]]
        elif fault == "slots_swapped":
            cache.state["ssm"] = [a[::-1] for a in cache.state["ssm"]]
        return answers, cache
    if fault == "too_few_decisive":
        cell["check"]["forced"] = dict(spec["forced"], min_decisive=10 ** 6)
    else:
        monkeypatch.setattr(job, "drive_decode_program", faulty)
    engine = serving.GenerationEngine(model, max_slots=4,
                                      kv_buckets=[64, 128, 256],
                                      prefix_slots=0)
    server = serving.GenerationServer(engine=engine, warmup=False).start()
    try:
        check = job.check_against_reference(
            server, engine, model, cell, np.random.default_rng(5), vocab)
    finally:
        server.stop()
    assert not check["ok"]
    assert check["refused"] == {"ring_column": ["ring_err"],
                                "slots_swapped": ["state_err"],
                                "too_few_decisive": ["decisive_positions"]
                                }[fault]


def test_soak_leaves_the_rows_at_the_bucket_of_the_mixs_longest_request(
        tmp_path, monkeypatch):
    import numpy as np
    from mxnet_tpu import metrics, serving
    job, model, _, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch)
    engine = serving.GenerationEngine(model, max_slots=4,
                                      kv_buckets=[64, 128, 256],
                                      prefix_slots=0, max_tokens=200)
    server = serving.GenerationServer(engine=engine, warmup=False).start()
    rng = np.random.default_rng(1)
    try:
        # nothing to climb to: no request is made
        job.soak(server, engine, {"prompt": {"max": 40},
                                  "output": {"max": 20}}, rng, vocab)()
        assert engine.cache.bucket == 64
        migrations = metrics.value("mxnet_gen_kv_migrations_total")
        # 40 + 150 positions need the 256 bucket: the pilot decodes,
        # through the engine, past 128
        cancel = job.soak(server, engine, {"prompt": {"max": 40},
                                           "output": {"max": 150}},
                          rng, vocab)
        assert engine.cache.bucket == 256
        assert engine.cache.occupancy() == 1
        assert metrics.value("mxnet_gen_kv_migrations_total") \
            == migrations + 2
        cancel()
    finally:
        server.stop()


def test_the_new_cell_resolves_from_the_real_benchmark():
    cell = "phi4_mini_flash.serve_reason"
    found = run.resolve(ROOT, cell)
    bench = found["bench"]
    listed = lastline.cell_metrics(bench, cell, 1)
    assert set(found["readers"]) == set(listed)
    for name, reader in found["readers"].items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            entry["layer"], entry["moves"], entry["unit"], entry["source"])
    assert found["chips"] == 1 and found["cell"]["engine"] == {
        "max_slots": 64, "kv_buckets": [1024, 2048, 4096],
        "prefix_slots": 0, "queue_limit": 100000, "max_tokens": 3000}
    # the accepted share of the roofline reads this cell's decode
    # program too, on the bytes the job supplies
    assert {"cache_bytes_per_slot", "state_install_ms",
            "decode_hbm_pct"} <= set(listed)
    # the forced sequences end at lengths the reference is compiled for
    check = found["cell"]["check"]
    assert {n + check["forced"]["steps"] for n in check["forced"]["prompts"]} \
        <= set(check["prompt_lengths"]) | {
            check["decode_prompt"] + check["new_tokens"] - 1}


def test_hybrid_step_bytes_by_hand():
    from chipbench.harness import hybrid_bytes
    arch = TINY[1]["arch"]
    state = 4 * 3 * 128 * (16 + 3)
    row = 2 * 2 * 16 * 4
    assert hybrid_bytes.state_bytes(arch) == state
    assert hybrid_bytes.row_bytes(arch, 4) == row
    # a slot at 5 (inside the window) and one at 20 (past it)
    assert hybrid_bytes.slot_bytes([5, 20], arch, 4) == \
        2 * 2 * state + (5 + 8) * 2 * row + 25 * 2 * row
    assert hybrid_bytes.live_row_equivalents([], arch, 4) is None
    assert hybrid_bytes.live_row_equivalents([[5], [5, 20]], arch, 4) == \
        (hybrid_bytes.slot_bytes([5], arch, 4)
         + hybrid_bytes.slot_bytes([5, 20], arch, 4)) / 2 / row
