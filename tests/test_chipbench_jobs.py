"""CPU rehearsal of the benchmark's newest job kind at a tiny size,
traced and untraced, as ``chipbench/tests/test_chipbench.py::
test_job_end_to_end_at_a_tiny_size`` rehearses the two older ones (that
file is run by hand; these count in tier-1).  They check control flow,
file lookup and the last line; no number they see is a statement about
speed."""
import json
import math
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
     "state_install_ms", "decode_hbm_pct", "decode_ahead_pct",
     "program_trace_lower_s", "program_load_s", "decode_device_ms"],
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


MOE_CELL = "tiny_moe.serve_agent"
MOE_TINY = (
    ["setup_s", "serve_tokens_per_s", "warmup_s", "decode_batch_mean",
     "kv_migrations", "decode_step_ms", "prefill_ms",
     "device_idle_pct.serve", "queue_wait_p95_ms", "admission_ms",
     "decode_dispatch_ms", "engine_host_ms", "idle_pct.decode_call",
     "idle_pct.admission", "idle_pct.engine_host", "cache_bytes_per_slot",
     "state_install_ms", "decode_hbm_pct", "decode_ahead_pct",
     "rows_read_pct", "expert_tokens_mean", "experts_hit_pct",
     "expert_load_max_over_mean", "moe_gmm_roofline_pct"],
    {"arch": {"layers": 4, "width": 64, "heads": 8, "kv_heads": 2,
              "head_dim": 16, "expert_width": 32, "experts": 16,
              "experts_held": 4, "experts_per_token": 4,
              "shared_experts": 2, "vocab": 512, "window": 8,
              "window_layers": 3, "full_layers": 1},
     "zoo": "mxnet_tpu.gluon.model_zoo.cohere2moe:get_cohere2moe",
     "zoo_args": ["tiny"], "zoo_kwargs": {"dtype": "float32"},
     "serve_dtype": "float32"},
    {"job": "serve_moe",
     "engine": {"max_slots": 4, "kv_buckets": [64, 128, 256],
                "prefix_slots": 0, "queue_limit": 1000, "max_tokens": 64},
     "traffic": {"rate_per_s": 20.0, "ramp_s": 0.5,
                 "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                 "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                 "at_window_end": "drain", "drain_s": 20.0},
     # forced: a slot that crosses the window of 8 and one installed from
     # the reference past the longest prompt the model prefills (256),
     # which takes the rows past the engine's last bucket
     "check": {"prompt_lengths": [5, 40],
               "forced": {"prompts": [3, 260], "copies": 1, "steps": 6,
                          "min_decisive": 3},
               "decode_prompt": 4, "new_tokens": 12,
               "batch_prompts": [8, 60]},
     "trace_at_s": 0.2, "trace_window_s": 0.5})
LOOP_CELL = "tiny_ouro.serve_math"
LOOP_TINY = (
    ["setup_s", "serve_tokens_per_s", "warmup_s", "decode_batch_mean",
     "kv_migrations", "decode_step_ms", "prefill_ms",
     "device_idle_pct.serve", "queue_wait_p95_ms", "admission_ms",
     "decode_dispatch_ms", "engine_host_ms", "idle_pct.decode_call",
     "idle_pct.admission", "idle_pct.engine_host", "cache_bytes_per_slot",
     "decode_hbm_pct", "decode_ahead_pct", "rows_read_pct",
     "loop_passes_per_token", "decode_attn_roofline_pct"],
    {"arch": {"layers": 3, "loop_steps": 3, "width": 64, "heads": 4,
              "kv_heads": 4, "head_dim": 16, "ffn": 96, "vocab": 512},
     "zoo": "mxnet_tpu.gluon.model_zoo.ouro:get_ouro",
     "zoo_args": ["tiny"], "zoo_kwargs": {"dtype": "float32"},
     "serve_dtype": "float32"},
    {"job": "serve_loop",
     # one bucket, as the real cell; its block is 512, so the slot
     # installed at 500 crosses it
     "engine": {"max_slots": 4, "kv_buckets": [1024], "prefix_slots": 0,
                "queue_limit": 1000, "max_tokens": 64},
     "traffic": {"rate_per_s": 20.0, "ramp_s": 0.5,
                 "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                 "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                 "at_window_end": "drain",
                 # patience, not speed: beside five other workers the
                 # interpret-mode loop has taken 40 s for these requests
                 "drain_s": 120.0},
     # forced: a slot from a short prompt and one installed from the
     # reference (longer than the traffic's longest prompt) that crosses
     # the ragged kernel's 512-position block
     "check": {"prompt_lengths": [5, 40],
               "forced": {"prompts": [3, 508], "copies": 1, "steps": 6,
                          "min_decisive": 3},
               "decode_prompt": 4, "new_tokens": 12,
               "batch_prompts": [8, 60]},
     "trace_at_s": 0.2, "trace_window_s": 0.5})
TINIES = {CELL: TINY, MOE_CELL: MOE_TINY, LOOP_CELL: LOOP_TINY}


def tiny_root(tmp_path, monkeypatch, cell=CELL):
    monkeypatch.setitem(rehearsal.TINY, cell, TINIES[cell])
    return rehearsal.tiny_root(tmp_path, cell)


def synthetic_devices(monkeypatch):
    """On the CPU the profiler records no device plane: keep the real
    trace's marker and put synthetic device events inside it."""
    real = trace_reduce.read_xplane

    def fake(path):
        _, (lo, hi) = real(path)
        q = (hi - lo) // 8
        return {"/device:TPU:0": {
            trace_reduce.OPS_LINE: [
                ("fusion.7", lo + 2 * q, 2 * q),
                ("gmm.3[tpu_custom_call]", lo + 5 * q, q),
                ("ragged_attention.9[tpu_custom_call]", lo + 6 * q, q)],
            trace_reduce.MODULES_LINE: [("jit__step(1)", lo + q, 4 * q)],
        }}, (lo, hi)
    monkeypatch.setattr(trace_reduce, "read_xplane", fake)
    v5e = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: v5e)


def _tiny_margin(monkeypatch, job):
    """A tiny router's 16 scores lie within 0.04 of each other, so the
    chip's margin for an unsettled choice would leave no token settled;
    float32 against float32 needs none to speak of."""
    if hasattr(job, "MARGIN"):
        monkeypatch.setattr(job, "MARGIN", 1e-5)


def _end_to_end(tmp_path, monkeypatch, cell, trace):
    import jax
    root, bench = tiny_root(tmp_path, monkeypatch, cell)
    if trace:
        synthetic_devices(monkeypatch)
    found = run.resolve(root, cell)
    _tiny_margin(monkeypatch, found["job"])
    line, units = run.measure(found, cell, 3_000_000_001, 1.5, trace,
                              jax.devices()[:1], time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert units == lastline.cell_metrics(bench, cell, trace)
    assert set(line["metrics"]) == set(units)
    line["device"].update(platform="tpu", memory_peak_bytes=1)
    lastline.validate(line, units, 1, trace)
    return {k: m["value"] for k, m in line["metrics"].items()}


def _family_programs(family):
    """After a rehearsed job: the table lists every program the family's
    engine built with a role, each with its stages' seconds."""
    from mxnet_tpu import tracing
    mine = [p for p in tracing.programs()
            if p.role and p.family in (family, None) and p.built()]
    roles = {p.role for p in mine}
    assert {"decode", "prefill", "select", "cache_write"} <= roles, roles
    assert all(p.seconds("trace", "lower") > 0 for p in mine)
    return roles


@pytest.mark.parametrize("trace", [0, 1])
def test_new_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch, trace):
    value = _end_to_end(tmp_path, monkeypatch, CELL, trace)
    if not trace:
        return
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
    # the program table's readers: the family's own programs, found by
    # role; the reference's layer programs and the eager initialisers
    # are in the table too (role None) and in neither sum
    from mxnet_tpu import tracing
    with_role = [p for p in tracing.programs() if p.role]
    assert {"decode", "prefill", "select", "cache_write",
            "cache_install"} <= {p.role for p in with_role}
    assert 0 < value["program_trace_lower_s"] == pytest.approx(
        sum(p.seconds("trace", "lower") for p in with_role))
    assert 0 < value["program_load_s"] == pytest.approx(
        sum(p.seconds("compile", "load") for p in with_role))
    others = sum(p.seconds() for p in tracing.programs() if not p.role)
    assert others > 0
    # the synthetic trace ran jit__step once for half the window
    assert value["decode_device_ms"] == pytest.approx(0.5e3 * 0.5, rel=0.2)


def _check_on_a_tiny_model(tmp_path, monkeypatch, cell=CELL):
    found = run.resolve(tiny_root(tmp_path, monkeypatch, cell)[0], cell)
    job, config = found["job"], found["config"]
    _tiny_margin(monkeypatch, job)
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


# ---------------------------------------------------------------------------
# job kind serve_moe (the Command A+ family), the same rehearsals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_moe_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch,
                                               trace):
    value = _end_to_end(tmp_path, monkeypatch, MOE_CELL, trace)
    if not trace:
        return
    # 4 slots, 4 of 16 experts held, 4 choices a token: a quarter of
    # the choices fall here, spread over 4 layers x 4 experts
    assert 0 < value["expert_tokens_mean"] <= 4
    assert 0 < value["experts_hit_pct"] <= 100
    assert value["expert_load_max_over_mean"] >= 1
    assert 0 < value["decode_hbm_pct"] and 0 < value["rows_read_pct"] <= 100
    assert 0 < value["moe_gmm_roofline_pct"]
    assert 0 < value["decode_ahead_pct"] < 100
    # three rings of 8 and the rows at some bucket, 32 channels, float32
    per_slot = value["cache_bytes_per_slot"]
    assert (per_slot - 3 * 2 * 32 * 8 * 4) / (2 * 32 * 4) in (64, 128, 256)
    assert "cache_install" in _family_programs("cohere2moe")


def test_the_float8_control_is_refused_by_the_moe_jobs_verdict(
        tmp_path, monkeypatch):
    """``chipbench/precision.py`` works on a ``serve_moe`` cell as it
    is: the reference on rounded weights goes through the job's
    ``verdict`` and is refused by its limits; against itself it is
    correct."""
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import precision
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     MOE_CELL)
    # 4 layers of width 64 gather less of a rounding than 4 of 4096:
    # the tiny control rounds to float8_e5m2 (as the hybrid family's)
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


@pytest.mark.parametrize("fault", ["ring_column", "rows_shifted",
                                   "load_shifted", "too_few_decisive"])
def test_the_moe_program_check_refuses_a_planted_fault(
        tmp_path, monkeypatch, fault):
    """The programs driven directly: a ring left one column off, the
    rows one position off, the load counted on the wrong experts, and a
    run with nothing decisive to compare each come out as not correct,
    by the limit that is for it."""
    import jax.numpy as jnp
    import numpy as np
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     MOE_CELL)
    cell = {"check": dict(spec)}
    drive = job.drive_decode_program

    def faulty(model, cache, forced):
        answers, loads = drive(model, cache, forced)
        if fault == "ring_column":
            cache.state["wk"] = [jnp.roll(a, 1, axis=2)
                                 for a in cache.state["wk"]]
        elif fault == "rows_shifted":
            cache._v = [jnp.roll(a, 1, axis=2) for a in cache._v]
        elif fault == "load_shifted":
            loads = [np.roll(a, 1, axis=1) for a in loads]
        return answers, loads
    if fault == "too_few_decisive":
        cell["check"]["forced"] = dict(spec["forced"], min_decisive=10 ** 6)
    else:
        monkeypatch.setattr(job, "drive_decode_program", faulty)
    readings = job.check_programs(
        model, (4, (64, 128, 256), (64, 128, 256)), cell,
        np.random.default_rng(5), vocab)
    assert readings["crossed_window_at"] == [3 + 6, 260 + 6]
    ok, refused = job.verdict(readings, cell["check"]["forced"]["min_decisive"])
    assert not ok and refused == {
        "ring_column": ["ring_err"], "rows_shifted": ["rows_err"],
        "load_shifted": ["route_excess"],
        "too_few_decisive": ["decisive_positions"]}[fault]


def test_a_sound_moe_program_check_moves_no_choice(tmp_path, monkeypatch):
    """float32 against the float32 reference: every step's load is the
    reference's, every decisive token its argmax, and the slot installed
    from the reference crossed the window and the engine's last
    bucket."""
    import numpy as np
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     MOE_CELL)
    readings = job.check_programs(
        model, (4, (64, 128, 256), (64, 128, 256)), {"check": spec},
        np.random.default_rng(6), vocab)
    assert job.verdict(readings, spec["forced"]["min_decisive"]) == (True, [])
    assert readings["route_moved"] == readings["route_excess"] == 0
    assert max(readings["ring_err"] + readings["rows_err"]
               + readings["prefill_logit_err"]) < 2e-5
    assert readings["crossed_window_at"] == [9, 266]


def test_route_reading_by_hand(monkeypatch):
    import numpy as np
    from chipbench.jobs import serve_moe as job
    monkeypatch.setattr(job, "MARGIN", 0.02)
    cfg = {"experts_held": (1, 3), "top_k": 2}
    # two tokens, four experts, two layers; the threshold lies midway
    # between a row's second and third score
    first = np.array([[0.9, 0.8, 0.1, 0.2],         # chooses 0, 1
                      [0.1, 0.5, 0.51, 0.9]])       # chooses 2, 3; 1 near
    second = np.array([[0.9, 0.1, 0.8, 0.2],        # chooses 0, 2
                       [0.9, 0.8, 0.1, 0.2]])       # chooses 0, 1
    chosen, near = job.held_choices(first, cfg)
    assert chosen.tolist() == [[True, False], [False, True]]
    assert near.tolist() == [0, 2]
    tainted = job.tainted_at([first, second], cfg)
    assert tainted.tolist() == [[False, False], [False, True],
                                [False, True]]
    assert job.settled_rows([first, second], cfg).tolist() == [True, False]
    want = np.array([[1, 1], [1, 1]])
    # the reference's own load: nothing moved; the room is the first
    # layer's two unsettled choices and, for the token they taint, top_k
    # in the second
    assert job.route_reading(want, [first, second], cfg) == (0, 4, 0)
    # the near choice went the other way, and its token anywhere after
    assert job.route_reading(np.array([[2, 0], [0, 2]]),
                             [first, second], cfg) == (4, 4, 0)
    # loads no unsettled choice explains
    assert job.route_reading(np.array([[0, 4], [1, 1]]),
                             [first, second], cfg) == (4, 4, 2)
    assert job.route_reading(np.array([[1, 1], [4, 1]]),
                             [first, second], cfg) == (3, 4, 1)
    # K and V are compared where the layer's input cannot have moved
    held = {"kv": [(np.ones((2, 1, 2)),) * 2] * 2,
            "scores": [first, second]}
    cfg = dict(cfg, kinds=["window", "full"], window=8)
    want = job.reference_holding(held, 2, cfg)
    assert want["ring_ok"].tolist() == [True, True]
    assert want["rows_ok"].tolist() == [True, False]
    got = {"ring": [np.ones((2, 2))] * 2,
           "rows": [np.array([[1.0, 1.0], [9.0, 9.0]])] * 2}
    assert job.holding_errs(got, want) == {"ring_err": [0.0, 0.0],
                                           "rows_err": [0.0, 0.0]}


def test_the_moe_cell_resolves_from_the_real_benchmark():
    cell = "command_a_plus.serve_agent"
    found = run.resolve(ROOT, cell)
    bench = found["bench"]
    listed = lastline.cell_metrics(bench, cell, 1)
    assert set(found["readers"]) == set(listed)
    for name, reader in found["readers"].items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            entry["layer"], entry["moves"], entry["unit"], entry["source"])
    assert found["chips"] == 1 and found["cell"]["engine"] == {
        "max_slots": 48, "kv_buckets": [1024, 2048, 4096],
        "prefix_slots": 0, "queue_limit": 100000, "max_tokens": 3000}
    assert {"decode_hbm_pct", "rows_read_pct", "expert_tokens_mean",
            "experts_hit_pct", "expert_load_max_over_mean",
            "moe_gmm_roofline_pct", "cache_bytes_per_slot"} <= set(listed)
    assert lastline.cell_metrics(bench, cell, 0) == {
        "setup_s": "s", "serve_tokens_per_s": "tokens/s"}
    # the configuration: every published number but the three that are
    # cut, and the share the zoo's spec holds
    config, entry = found["config"], next(
        c for c in bench["configs"] if c["name"] == "command_a_plus")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_experts_per_tok"],
            config["num_shared_experts"], config["sliding_window"]) == (
        4096, 4096, 128, 8, 128, 8, 4, 4096)
    assert len(config["layer_types"]) == 32
    from mxnet_tpu.gluon.model_zoo import cohere2moe as c2
    net = c2.get_cohere2moe(*config["zoo_args"])
    arch, cfg = config["arch"], net.config
    assert (cfg["num_layers"], cfg["experts_held"], cfg["vocab_rows"]) == (
        config["num_hidden_layers"], (0, config["num_experts"]),
        config["vocab_size"]) == (arch["layers"], (0, arch["experts_held"]),
                                  arch["vocab"])
    assert cfg["kinds"] == ["window" if t == "sliding_attention" else "full"
                            for t in config["layer_types"][:4]]
    assert (cfg["num_experts"], cfg["top_k"], cfg["window"]) == (
        config["published"]["num_experts"], config["num_experts_per_tok"],
        config["sliding_window"])
    # the two slots installed from the reference cross the window
    # inside lengths the reference is compiled for
    check, job = found["cell"]["check"], found["job"]
    ends = [n + check["forced"]["steps"] for n in check["forced"]["prompts"]]
    assert max(ends) > config["sliding_window"] > max(
        check["forced"]["prompts"]) and max(ends) <= max(job.REF_LENGTHS)


def test_moe_step_bytes_by_hand():
    from chipbench.harness import moe_bytes
    from mxnet_tpu.gluon.model_zoo import cohere2moe as c2
    arch = MOE_TINY[1]["arch"]
    expert = 3 * 64 * 32 * 4
    assert moe_bytes.expert_bytes(arch, 4) == expert
    assert moe_bytes.row_bytes(arch, 4) == 2 * 2 * 16 * 4
    # every weight outside the routed experts: from the zoo's shapes
    net = c2.get_cohere2moe("tiny")
    outside = sum(math.prod(p.shape)
                  for name, p in net.collect_params().items()
                  if "expert_in" not in name and "expert_out" not in name)
    assert moe_bytes.fixed_bytes(arch, 4) == 4 * outside
    assert moe_bytes.step_weight_bytes(2.5, arch, 4) == \
        4 * outside + 2.5 * expert
    # a slot at 5 (inside the window of 8) and one at 20 (past it):
    # three rings capped, the rows to the position
    assert moe_bytes.slot_rows([5, 20], arch) == 3 * (5 + 8) + 25
    assert moe_bytes.live_row_equivalents([], arch) is None
    assert moe_bytes.live_row_equivalents([[5], [5, 20]], arch) == \
        (3 * 5 + 5 + 3 * 13 + 25) / 2
    # the published share: 344.5 M outside the experts a layer, 50.3 M an
    # expert, 4096 B a K and V row
    real = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "command_a_plus.json")))["arch"]
    assert moe_bytes.expert_bytes(real, 2) == 2 * 50_331_648
    assert moe_bytes.row_bytes(real, 2) == 4096
    assert moe_bytes.fixed_bytes(real, 2) == 2 * (
        4_733_292_544 - 4 * 16 * 50_331_648)
    flops, nbytes = moe_bytes.gmm_flops_and_bytes(1000, 60, real, 2)
    assert flops == 6 * 1000 * 4096 * 4096
    assert nbytes == 60 * 2 * 50_331_648 + 1000 * (8192 * 2 + 12288 * 4)


# ---------------------------------------------------------------------------
# job kind serve_loop (the Ouro family), the same rehearsals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_loop_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch,
                                                trace):
    value = _end_to_end(tmp_path, monkeypatch, LOOP_CELL, trace)
    if not trace:
        return
    # 3 loop steps on every launch of the tiny model
    assert value["loop_passes_per_token"] == 3.0
    assert 0 < value["decode_hbm_pct"] and 0 < value["decode_attn_roofline_pct"]
    # 9 entries of 4 heads x 16, K and V, float32, one bucket of 1024
    assert value["cache_bytes_per_slot"] == 9 * 2 * 64 * 4 * 1024
    # every slot sits in the first of the bucket's eight blocks (the
    # appended walk's: 128 positions of 1024)
    assert value["rows_read_pct"] == 12.5
    _family_programs("loop")


def test_the_float8_control_is_refused_by_the_loop_jobs_verdict(
        tmp_path, monkeypatch):
    """``chipbench/precision.py`` works on a ``serve_loop`` cell as it
    is: the reference on rounded weights goes through the job's
    ``verdict`` and is refused by its limits; against itself it is
    correct."""
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import precision
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     LOOP_CELL)
    # 9 passes of width 64 gather less of a rounding than 192 of 2048:
    # the tiny control rounds to float8_e5m2 (as the other families')
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


@pytest.mark.parametrize("fault", ["entry_swapped", "rows_shifted",
                                   "a_pass_dropped", "too_few_decisive"])
def test_the_loop_program_check_refuses_a_planted_fault(
        tmp_path, monkeypatch, fault):
    """The programs driven directly: two entries of the cache swapped
    (loop steps 0 and 1 of layer 0: what a wrong entry index aliases),
    the rows one position off, a program that makes one loop step fewer
    than it says, and a run with nothing decisive to compare each come
    out as not correct, by the limit that is for it."""
    import jax.numpy as jnp
    import numpy as np
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     LOOP_CELL)
    cell = {"check": dict(spec), "traffic": {"prompt": {"max": 64}}}
    drive = job.drive_decode_program

    def faulty(model, cache, forced):
        answers = drive(model, cache, forced)
        if fault == "entry_swapped":
            n = model.cfg["num_layers"]
            cache._k = [cache._k[0].at[jnp.array([0, n])].set(
                cache._k[0][jnp.array([n, 0])])]
        elif fault == "rows_shifted":
            cache._v = [jnp.roll(cache._v[0], 1, axis=3)]
        return answers
    if fault == "too_few_decisive":
        cell["check"]["forced"] = dict(spec["forced"], min_decisive=10 ** 6)
    elif fault == "a_pass_dropped":
        from mxnet_tpu.serving.loop import LoopDecodeModel
        short = dict(model.cfg, loop_steps=model.cfg["loop_steps"] - 1)
        dropped = LoopDecodeModel(model.params, short, model.max_length,
                                  "faulty")
        # it still says, and holds the cache of, every loop step
        dropped.cfg, dropped.entries = model.cfg, model.entries
        model = dropped
    else:
        monkeypatch.setattr(job, "drive_decode_program", faulty)
    readings = job.check_programs(
        model, (4, (1024,), (64, 128, 256, 512, 1024)), cell,
        np.random.default_rng(5), vocab)
    assert readings["crossed_block_at"] == [508 + 6]
    ok, refused = job.verdict(readings, cell["check"]["forced"]["min_decisive"])
    assert not ok and set(refused) >= {
        "entry_swapped": {"rows_err"}, "rows_shifted": {"rows_err"},
        # the first two entries are sound; every position of the last
        # has parted, and no prompt's last position is settled
        "a_pass_dropped": {"prefill_logit_err", "unsettled_share"},
        "too_few_decisive": {"decisive_positions"}}[fault]


def test_a_sound_loop_program_check_is_correct(tmp_path, monkeypatch):
    import numpy as np
    job, model, spec, vocab = _check_on_a_tiny_model(tmp_path, monkeypatch,
                                                     LOOP_CELL)
    cell = {"check": spec, "traffic": {"prompt": {"max": 64}}}
    readings = job.check_programs(
        model, (4, (1024,), (64, 128, 256, 512, 1024)), cell,
        np.random.default_rng(5), vocab)
    assert job.verdict(readings, spec["forced"]["min_decisive"]) == (True, [])
    assert max(readings["rows_err"]) < 2e-5
    assert max(readings["prefill_logit_err"]) < 2e-5
    # 2 prefills and 4 slots after the steps: K and V of the first two
    # compared entries over all positions; of the last entry, the share
    # of positions that have parted: none, float32 against float32
    assert len(readings["rows_err"]) == (2 + 4) * 2 * 2
    assert readings["unsettled_by_array"] == [0.0] * (2 + 4)
    # a 0.0 a compared position: the two prefills' and the four slots'
    assert set(readings["unsettled_share"]) == {0.0}
    assert len(readings["unsettled_share"]) > 5 + 40 + 4 * 6
    assert readings["prompts_redrawn"] == 0


def test_the_loop_cell_resolves_from_the_real_benchmark():
    cell = "ouro_2_6b.serve_math"
    found = run.resolve(ROOT, cell)
    bench = found["bench"]
    listed = lastline.cell_metrics(bench, cell, 1)
    assert set(found["readers"]) == set(listed)
    for name, reader in found["readers"].items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            entry["layer"], entry["moves"], entry["unit"], entry["source"])
    assert found["chips"] == 1 and found["cell"]["engine"] == {
        "max_slots": 5, "kv_buckets": [1024], "prefix_slots": 0,
        "queue_limit": 100000, "max_tokens": 752}
    # the sixteen serving readers ISSUE 35 lists, the two it adds,
    # warmup_s, which every cell reports, and three of ISSUE 37 (two of
    # set-up, the decode program's device time); not state_install_ms:
    # a slot of this family holds rows alone; not admission_device_ms:
    # a 4 s stretch of this cell often holds no admission
    assert len(listed) == 16 + 2 + 1 + 3 \
        and not {"state_install_ms", "admission_device_ms"} & set(listed)
    assert {"loop_passes_per_token", "decode_attn_roofline_pct",
            "decode_hbm_pct", "rows_read_pct", "cache_bytes_per_slot"} \
        <= set(listed)
    assert lastline.cell_metrics(bench, cell, 0) == {
        "setup_s": "s", "serve_tokens_per_s": "tokens/s"}
    # the cell's own entry: one chip (what other cells the benchmark
    # holds, and on how many chips, is theirs to say)
    assert next(w for w in bench["workloads"]
                if w["name"] == cell)["chips"] == 1
    mix = found["cell"]["traffic"]
    assert (mix["prompt"], mix["output"], mix["ramp_s"],
            mix["at_window_end"]) == (
        {"median": 96, "sigma": 0.5, "min": 32, "max": 256},
        {"median": 384, "sigma": 0.35, "min": 192, "max": 752}, 20.0,
        "cancel")
    # the configuration: every number of the published config, nothing cut
    config, entry = found["config"], next(
        c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == config["reduced"] == []
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_hidden_layers"],
            config["total_ut_steps"], config["early_exit_threshold"],
            config["vocab_size"], config["rope_theta"],
            config["rms_norm_eps"], config["tie_word_embeddings"]) == (
        2048, 5632, 16, 16, 128, 48, 4, 1, 49152, 1000000, 1e-06, False)
    assert len(config["layer_types"]) == 48
    from mxnet_tpu.gluon.model_zoo import ouro
    net = ouro.get_ouro(*config["zoo_args"])
    assert (net.config["num_layers"], net.config["loop_steps"],
            net.config["units"], net.config["hidden_size"]) == (
        48, 4, 2048, 5632)
    # the longest request of the mix fits the one bucket
    assert mix["prompt"]["max"] + mix["output"]["max"] \
        <= found["cell"]["engine"]["kv_buckets"][0]


# ---------------------------------------------------------------------------
# the readers of the program's table (chipbench/harness/program_table.py)
# on a table and reductions made by hand
# ---------------------------------------------------------------------------

NEW_READERS = ("program_trace_lower_s", "program_load_s",
               "decode_device_ms", "admission_device_ms",
               "device_ms_per_step.attn", "device_ms_per_step.ffn",
               "device_ms_per_step.optim", "device_ms_per_step.other",
               "device_unscoped_pct")


def _reader(name):
    return run._load_module(os.path.join(ROOT, "chipbench", "metrics",
                                         name + ".py"))


def _hand_table(monkeypatch, scopes=None):
    """A table as the program would hold it after a warm start: the
    family's programs with a role, the reference's without, one entry
    that a reading of the HLO caused."""
    from chipbench.harness import program_table
    from mxnet_tpu import tracing

    class Step(tracing.Program):
        def scopes(self, shape=-1):
            return scopes

    def rec(cls, module, role, *shapes):
        p = cls(module, role, "toy")
        p.shapes = list(shapes)
        return p

    table = [
        rec(Step, "jit_step", "train_step",
            {"args": "a", "trace_s": 2.0, "lower_s": 1.0, "load_s": 4.0},
            {"args": "a", "trace_s": 9.0, "lower_s": 9.0, "load_s": 9.0,
             "reading": True}),
        rec(tracing.Program, "jit__step", "decode",
            {"args": "b", "trace_s": 0.5, "lower_s": 0.25,
             "compile_s": 8.0}),
        rec(tracing.Program, "jit__prefill", "prefill",
            {"args": "c", "trace_s": 0.25, "lower_s": 0.125,
             "load_s": 1.0}),
        rec(tracing.Program, "jit_write", "cache_write"),
        rec(tracing.Program, "jit_install", "cache_install"),
        rec(tracing.Program, "jit__select_one", "select"),
        rec(tracing.Program, "jit_ref_logits", None,
            {"args": None, "builds": 1, "trace_s": 100.0,
             "compile_s": 100.0}),
    ]
    monkeypatch.setattr(program_table, "table", lambda: table)
    return table


def _hand_ctx(**reduction):
    return {"reduction": reduction or None,
            "readings": {"traced_steps": 4}}


def test_the_setup_readers_sum_the_roles_and_leave_the_reading_out(
        monkeypatch):
    _hand_table(monkeypatch)
    ctx = _hand_ctx()
    assert _reader("program_trace_lower_s").read(ctx) == pytest.approx(
        2.0 + 1.0 + 0.5 + 0.25 + 0.25 + 0.125)
    assert _reader("program_load_s").read(ctx) == pytest.approx(
        4.0 + 8.0 + 1.0)


def test_the_serving_readers_find_their_programs_by_role(monkeypatch):
    _hand_table(monkeypatch)
    ctx = _hand_ctx(ops={}, programs={
        "jit__step": [0.8, 40.0], "jit__prefill": [0.09, 3.0],
        "jit_write": [0.006, 3.0], "jit_install": [0.003, 3.0],
        "jit__select_one": [0.0009, 3.0], "jit_ref_logits": [5.0, 1.0]})
    assert _reader("decode_device_ms").read(ctx) == pytest.approx(20.0)
    assert _reader("admission_device_ms").read(ctx) == pytest.approx(
        1e3 * (0.09 + 0.006 + 0.003 + 0.0009) / 3.0)
    # a stretch without an admission has nothing to divide by
    ctx = _hand_ctx(ops={}, programs={"jit__step": [0.8, 40.0]})
    assert _reader("admission_device_ms").read(ctx) is None


def test_the_training_readers_add_up_to_the_scoped_ops_seconds(
        monkeypatch):
    scopes = {
        "fusion.1": ("ffn", "up", "bwd"), "fusion.2": ("ffn", "down", "fwd"),
        "custom-call.3": ("attn", "core", "fwd"),
        "fusion.4": ("attn", "qkv", "fwd"), "fusion.5": ("optim", "", "fwd"),
        "fusion.6": ("head", "", "fwd"), "fusion.7": ("loss", "", "bwd"),
        "fusion.8": ("norm", "", "fwd"), "copy.9": ("unscoped", "", "fwd"),
    }
    _hand_table(monkeypatch, scopes)
    ops = {"fusion.1": [0.40, 4], "fusion.2": [0.20, 4],
           "custom-call.3[tpu_custom_call]": [0.12, 4],
           "fusion.4": [0.08, 4], "fusion.5": [0.04, 4],
           "fusion.6": [0.02, 4], "fusion.7": [0.01, 4],
           "fusion.8": [0.01, 4], "copy.9": [0.03, 4],
           "fusion.77": [0.01, 1],          # not the step's: unscoped
           "while.3": [0.5, 4]}             # around its children: left out
    ctx = _hand_ctx(ops=ops, programs={"jit_step": [0.95, 4.0],
                                       "jit_convert": [0.002, 1.0]})
    got = {c: _reader(f"device_ms_per_step.{c}").read(ctx)
           for c in ("attn", "ffn", "optim", "other")}
    assert got == pytest.approx({"attn": 50.0, "ffn": 150.0, "optim": 10.0,
                                 "other": 10.0})
    scoped = sum(s for n, (s, _) in ops.items()
                 if n not in ("copy.9", "fusion.77", "while.3"))
    assert sum(got.values()) * 4 / 1e3 == pytest.approx(scoped)
    # unscoped + what the unregistered jit_convert may have added, over
    # all leaf ops' seconds
    assert _reader("device_unscoped_pct").read(ctx) == pytest.approx(
        100.0 * (0.03 + 0.01 + 0.002) / (scoped + 0.04))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_given_the_parents_shape_of_things_returns_none(
        monkeypatch, name):
    """The parent has no program table (``tracing.programs``): every
    new reader leaves its metric out and does not raise, traced or
    not."""
    from mxnet_tpu import tracing
    monkeypatch.delattr(tracing, "programs")
    ctx = _hand_ctx(ops={"fusion.1": [0.4, 4]},
                    programs={"jit__step": [0.8, 40.0],
                              "jit__prefill": [0.09, 3.0]})
    assert _reader(name).read(ctx) is None
    assert _reader(name).read(_hand_ctx()) is None


def test_benchmark_json_lists_the_new_readers_for_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # all there, in their order, wherever later metrics put them
    names = [m["name"] for m in bench["per_layer"]]
    assert set(NEW_READERS) <= set(names)
    assert [n for n in names if n in NEW_READERS] == list(NEW_READERS)
    cells = [w["name"] for w in bench["workloads"]]
    serve = [c for c in cells if ".serve_" in c]
    for name in NEW_READERS:
        mod, entry = _reader(name), entries[name]
        assert (entry["unit"], entry["layer"], entry["moves"],
                entry["source"], entry["better"]) == (
            mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE, "lower")
        want = cells if name.startswith("program_") else serve \
            if name.endswith("_device_ms") else ["bert_large.train_mlm512"]
        if name == "admission_device_ms":
            # 0.3 admissions a second: one traced stretch in three of
            # the looped cell holds none, so the cell does not list it
            want = [c for c in serve if c != "ouro_2_6b.serve_math"]
        assert entry["workloads"] == want
