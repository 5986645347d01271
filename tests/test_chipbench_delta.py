"""CPU rehearsal of the benchmark's ``serve_delta`` job kind (the Olmo
Hybrid family) at a tiny size, traced and untraced, with its check's
planted faults and its two controls, and the readers this family adds:
control flow, file lookup and the last line; no number they see is a
statement about speed."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402
from chipbench.harness import (delta_bytes, flops, lastline,  # noqa: E402
                               trace_reduce)

pytestmark = pytest.mark.host_mesh

rehearsal = run._load_module(os.path.join(ROOT, "chipbench", "tests",
                                          "test_chipbench.py"))

CELL = "tiny_olmo.serve_chat"
TINY_ARCH = {"layers": 4, "linear_layers": 3, "full_layers": 1,
             "width": 64, "ffn": 96, "vocab": 97, "heads": 2,
             "head_dim": 32, "lin_heads": 2, "key_dim": 8,
             "value_dim": 48, "conv_size": 4, "rms_norm_eps": 1e-06}
TINY = (
    ["setup_s", "serve_tokens_per_s", "warmup_s", "decode_batch_mean",
     "kv_migrations", "decode_step_ms", "prefill_ms",
     "device_idle_pct.serve", "queue_wait_p95_ms", "admission_ms",
     "decode_dispatch_ms", "engine_host_ms", "idle_pct.decode_call",
     "idle_pct.admission", "idle_pct.engine_host", "cache_bytes_per_slot",
     "state_install_ms", "decode_hbm_pct", "decode_ahead_pct",
     "rows_read_pct", "decode_attn_roofline_pct", "delta_step_roofline_pct",
     "program_trace_lower_s", "program_load_s", "decode_device_ms"],
    {"arch": TINY_ARCH,
     "zoo": "mxnet_tpu.gluon.model_zoo.olmo_hybrid:get_olmo_hybrid",
     "zoo_args": ["tiny"], "zoo_kwargs": {"dtype": "float32"},
     "serve_dtype": "float32"},
    {"job": "serve_delta",
     # two buckets, as the real cell: the slot from a 508-token prompt
     # crosses the first (and the ragged kernel's 512-position block),
     # and the soak's 500-token request grows the rows to the second
     "engine": {"max_slots": 4, "kv_buckets": [512, 1024], "prefix_slots": 0,
                "queue_limit": 1000, "max_tokens": 64},
     "traffic": {"rate_per_s": 20.0, "ramp_s": 0.5,
                 "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                            "max": 500},
                 "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                 "at_window_end": "drain", "drain_s": 120.0},
     "check": {"prompt_lengths": [5, 40],
               "forced": {"prompts": [3, 508], "copies": 1, "steps": 6,
                          "min_decisive": 3},
               "decode_prompt": 4, "new_tokens": 12,
               "batch_prompts": [8, 60]},
     "trace_at_s": 0.2, "trace_window_s": 0.5})


def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setitem(rehearsal.TINY, CELL, TINY)
    return rehearsal.tiny_root(tmp_path, CELL)


def synthetic_devices(monkeypatch):
    """On the CPU the profiler records no device plane: keep the real
    trace's marker and put synthetic device events inside it, the two
    kernels the family's step calls among them."""
    real = trace_reduce.read_xplane

    def fake(path):
        _, (lo, hi) = real(path)
        q = (hi - lo) // 8
        return {"/device:TPU:0": {
            trace_reduce.OPS_LINE: [
                ("fusion.7", lo + 2 * q, 2 * q),
                ("delta_step.2[tpu_custom_call]", lo + 4 * q, q),
                ("ragged_attention.9[tpu_custom_call]", lo + 6 * q, q)],
            trace_reduce.MODULES_LINE: [("jit__step(1)", lo + q, 4 * q)],
        }}, (lo, hi)
    monkeypatch.setattr(trace_reduce, "read_xplane", fake)
    v5e = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: v5e)


def _job_and_model(tmp_path, monkeypatch, seed=7):
    found = run.resolve(tiny_root(tmp_path, monkeypatch)[0], CELL)
    job, config = found["job"], found["config"]
    return job, job.build_model(config, seed), found["cell"], \
        config["arch"]["vocab"]


# the engine's shape of the tiny cell: slots, KV grid, prompt buckets
SHAPE = (4, (512, 1024), (64, 128, 256, 512, 1024))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_delta_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch,
                                                 trace):
    import jax
    root, bench = tiny_root(tmp_path, monkeypatch)
    if trace:
        synthetic_devices(monkeypatch)
    found = run.resolve(root, CELL)
    line, units = run.measure(found, CELL, 3_000_000_001, 1.5, trace,
                              jax.devices()[:1], time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == set(units)
    line["device"].update(platform="tpu", memory_peak_bytes=1)
    lastline.validate(line, units, 1, trace)
    if not trace:
        return
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert 0 < value["decode_hbm_pct"]
    assert 0 < value["delta_step_roofline_pct"]
    assert 0 < value["decode_attn_roofline_pct"]
    assert value["state_install_ms"] > 0
    # 3 linear layers' state (2 x 8 x 48 matrix + 3 x 112 conv inputs,
    # float32) and one full layer's rows at a bucket: the soak grew them
    # to the second, and the tiny mix's short requests empty the engine,
    # which gives them back to the first (the real cell's slots never
    # empty)
    fixed = 3 * 4 * (2 * 8 * 48 + 3 * (2 * 2 * 8 + 2 * 48))
    bucket = (value["cache_bytes_per_slot"] - fixed) / (2 * 64 * 4)
    assert bucket in (512, 1024)
    # every slot is in the bucket's first 512-position block
    assert value["rows_read_pct"] == 100.0 * 512 / bucket
    from mxnet_tpu import tracing
    roles = {p.role for p in tracing.programs()
             if p.role and p.family in ("olmo_hybrid", None) and p.built()}
    assert {"decode", "prefill", "select", "cache_write",
            "cache_install"} <= roles


def test_a_sound_program_check_is_correct(tmp_path, monkeypatch):
    job, model, cell, vocab = _job_and_model(tmp_path, monkeypatch)
    readings = job.check_programs(model, SHAPE, cell, np.random.default_rng(5),
                                  vocab)
    assert job.verdict(readings, cell["check"]["forced"]["min_decisive"]) \
        == (True, [])
    # float32 against float32: the chunks' solve and the kernel against
    # one token at a time
    assert max(readings["first_state_err"]) < 2e-5
    assert max(readings["state_err"]) < 2e-5
    assert max(readings["prefill_logit_err"]) < 2e-5
    # 2 prefills, 2 compared slots after the forced steps and the first
    # linear layer of the 2 other slots; one full layer, no deeper one
    assert len(readings["first_state_err"]) == 2 + 2 + 2
    assert len(readings["rows_err"]) == 4 * 2
    assert readings["deep_rows_err"] == []
    # the forced steps took the 508-token slot past the first bucket
    assert readings["forced_bucket"] == 1024


@pytest.mark.parametrize("fault", ["state_in_bfloat16", "conv_shifted",
                                   "rows_shifted", "too_few_decisive"])
def test_the_program_check_refuses_a_planted_fault(tmp_path, monkeypatch,
                                                   fault):
    """The programs driven directly: a decode step that keeps the matrix
    state in bfloat16, the conv's inputs one position off, the rows one
    position off, and a run with nothing decisive to compare each come
    out as not correct, by the limit that is for it."""
    import jax.numpy as jnp
    job, model, cell, vocab = _job_and_model(tmp_path, monkeypatch)
    cell = dict(cell, check=dict(cell["check"]))
    drive = job.drive_decode_program

    def faulty(model, cache, forced):
        if fault == "state_in_bfloat16":
            answers = []
            for toks in forced:
                cache.ensure_capacity(cache.needed_capacity())
                answers.append(model.step(cache, toks, cache.positions))
                cache.positions += 1
                cache.state["ssm"] = [s.astype(jnp.bfloat16)
                                      .astype(jnp.float32)
                                      for s in cache.state["ssm"]]
            return np.stack(answers)
        answers = drive(model, cache, forced)
        if fault == "conv_shifted":
            cache.state["conv"] = [jnp.roll(c, 1, axis=1)
                                   for c in cache.state["conv"]]
        elif fault == "rows_shifted":
            cache._v = [jnp.roll(v, 1, axis=2) for v in cache._v]
        return answers
    if fault == "too_few_decisive":
        cell["check"]["forced"] = dict(cell["check"]["forced"],
                                       min_decisive=10 ** 6)
    else:
        monkeypatch.setattr(job, "drive_decode_program", faulty)
    readings = job.check_programs(model, SHAPE, cell,
                                  np.random.default_rng(5), vocab)
    ok, refused = job.verdict(readings,
                              cell["check"]["forced"]["min_decisive"])
    assert not ok and set(refused) >= {
        "state_in_bfloat16": {"first_state_err"},
        "conv_shifted": {"conv_err"}, "rows_shifted": {"rows_err"},
        "too_few_decisive": {"decisive_positions"}}[fault]


def test_the_two_controls_are_refused_by_the_jobs_own_limits(
        tmp_path, monkeypatch):
    """``chipbench/precision.py``'s float8 reading goes through the job's
    ``verdict`` and is refused; the reference with its state kept in
    bfloat16 reads above the first linear layer's limit; the reference
    against itself is correct."""
    import types
    import jax
    import jax.numpy as jnp
    from chipbench import precision
    job, model, cell, vocab = _job_and_model(tmp_path, monkeypatch)
    spec = cell["check"]
    # 4 layers of width 64 gather less of a rounding than 16 of 3840:
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
    readings, over = job.state_control(model, spec, np.random.default_rng(3),
                                       vocab, jnp.bfloat16)
    assert "first_state_err" in over
    assert min(readings["first_state_err"]) > job.FIRST_STATE_TOL


def test_the_delta_cell_resolves_from_the_real_benchmark():
    cell = "olmo_hybrid_7b.serve_chat"
    found = run.resolve(ROOT, cell)
    bench = found["bench"]
    listed = lastline.cell_metrics(bench, cell, 1)
    assert set(found["readers"]) == set(listed)
    for name, reader in found["readers"].items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            entry["layer"], entry["moves"], entry["unit"], entry["source"])
    assert found["chips"] == 1 and found["cell"]["job"] == "serve_delta" \
        and found["cell"]["engine"] == {
            "max_slots": 40, "kv_buckets": [1024, 2048], "prefix_slots": 0,
            "queue_limit": 100000, "max_tokens": 1024}
    # the seventeen serving readers (state_install_ms among them),
    # warmup_s, four of the program table, the ragged kernel's share and
    # the delta kernel's
    assert len(listed) == 17 + 1 + 4 + 2
    assert {"delta_step_roofline_pct", "decode_attn_roofline_pct",
            "decode_hbm_pct", "rows_read_pct", "cache_bytes_per_slot",
            "state_install_ms", "admission_device_ms"} <= set(listed)
    assert lastline.cell_metrics(bench, cell, 0) == {
        "setup_s": "s", "serve_tokens_per_s": "tokens/s"}
    mix = found["cell"]["traffic"]
    assert (mix["prompt"], mix["output"], mix["at_window_end"]) == (
        {"median": 256, "sigma": 0.6, "min": 64, "max": 1024},
        {"median": 384, "sigma": 0.5, "min": 64, "max": 1024}, "cancel")
    # the longest request of the mix needs the second bucket, and the
    # soak takes the rows there before the window
    assert found["cell"]["engine"]["kv_buckets"][0] \
        < mix["prompt"]["max"] + mix["output"]["max"] \
        <= found["cell"]["engine"]["kv_buckets"][1]
    # the configuration: every number of the catalog's config, the depth
    # alone cut
    config, entry = found["config"], next(
        c for c in bench["configs"] if c["name"] == "olmo_hybrid_7b")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["vocab_size"],
            config["rms_norm_eps"], config["tie_word_embeddings"],
            config["linear_allow_neg_eigval"], config["num_hidden_layers"],
            config["rope_parameters"]) == (
        3840, 11008, 30, 30, 30, 30, 96, 192, 4, 100352, 1e-06, False, True,
        16, {"rope_theta": None})
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    from mxnet_tpu.gluon.model_zoo import olmo_hybrid
    net = olmo_hybrid.get_olmo_hybrid(*config["zoo_args"],
                                      **config["zoo_kwargs"])
    c = net.config
    assert (c["num_layers"], c["units"], c["hidden_size"], c["num_heads"],
            c["head_dim"], c["lin_heads"], c["key_dim"], c["value_dim"],
            c["conv_size"], c["allow_neg_eigval"], c["vocab_size"]) == (
        16, 3840, 11008, 30, 128, 30, 96, 192, 4, True, 100352)
    assert c["kinds"] == olmo_hybrid.layer_kinds(16, config["layer_types"])
    assert net.num_parameters() == 4_100_788_944
    arch = config["arch"]
    assert (arch["linear_layers"], arch["full_layers"]) == (
        c["kinds"].count("linear"), c["kinds"].count("full"))


def test_the_deep_rows_are_held_by_their_norm_over_the_positions(
        tmp_path, monkeypatch):
    """Two full layers: the later one's K and V are held by ||a - b|| /
    ||b||, which one position's deviation hardly moves and rows in the
    wrong column do; their largest deviation, and its position, are
    readings."""
    job, _, _, _ = _job_and_model(tmp_path, monkeypatch)
    rng = np.random.default_rng(0)
    want = {"state": [np.ones((2, 2, 2))], "conv": [np.ones((3, 4))],
            "rows": [rng.standard_normal((300, 64)) for _ in range(4)]}
    got = dict(want, rows=[a.copy() for a in want["rows"]])
    got["rows"][2][7, 5] += 0.3 * np.abs(want["rows"][2]).max()
    errs = job.holding_errs(got, want)
    peaks, at = job.deep_rows_peaks(got, want)
    assert errs["rows_err"] == [0.0, 0.0]
    assert peaks[0] == pytest.approx(0.3) and at == [7, 0]
    assert max(errs["deep_rows_err"]) < job.DEEP_ROWS_TOL
    got["rows"][3] = np.roll(want["rows"][3], 1, axis=0)
    errs = job.holding_errs(got, want)
    assert errs["deep_rows_err"][1] > 1.0
    readings = dict({name: [0.0] for name in job.LIMITS}, **errs,
                    decisive_positions=10, decisive_mismatches=0)
    assert job.verdict(readings, 6) == (False, ["deep_rows_err"])


def test_delta_step_bytes_by_hand():
    real = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "olmo_hybrid_7b.json")))["arch"]
    # a slot and linear layer: 30 x 96 x 192 float32 + 3 x 11520 float32
    assert delta_bytes.matrix_state_bytes(real) == 2_211_840
    assert delta_bytes.state_bytes(real) == 2_350_080
    assert delta_bytes.row_bytes(real, 2) == 15_360
    # the weights a step reads: all 4,100,788,944 parameters but the
    # embedding, A_log and dt_bias in float32
    assert delta_bytes.step_weight_bytes(real, 2) == 2 * (
        4_100_788_944 - 100352 * 3840) + 12 * 2 * 30 * 2
    # two samples: slots at 10 and 20, then one at 30
    samples = [[10, 20], [30]]
    row = 15_360
    want = ((2 * 2 * 12 * 2_350_080 + 30 * 4 * row)
            + (1 * 2 * 12 * 2_350_080 + 30 * 4 * row)) / 2 / row
    assert delta_bytes.live_row_equivalents(samples, real, 2) == \
        pytest.approx(want)
    assert delta_bytes.attn_bytes(samples, real, 2) == 30 * 4 * row
    assert delta_bytes.delta_state_bytes(samples, real) == \
        1.5 * 2 * 12 * 2_211_840
    assert delta_bytes.live_row_equivalents([], real, 2) is None


def _reader(name):
    return run._load_module(os.path.join(ROOT, "chipbench", "metrics",
                                         name + ".py"))


def test_delta_step_roofline_on_a_hand_made_reduction():
    reader = _reader("delta_step_roofline_pct")
    peaks = flops.peaks("TPU v5 lite")
    red = {"ops": {"delta_step.3[tpu_custom_call]": [0.040, 120],
                   "delta_step[tpu_custom_call]": [0.008, 24],
                   "fusion.1": [0.5, 10]},
           "programs": {"jit__step": [0.2, 10], "jit__prefill": [0.1, 2]}}
    ctx = {"reduction": red, "peaks": peaks,
           "readings": {"delta_state_bytes": 2 * 12 * 40 * 2_211_840}}
    least = 10 * 2 * 12 * 40 * 2_211_840 / peaks["hbm_bytes_per_s"]
    assert reader.read(ctx) == pytest.approx(100 * least / 0.048)
    # the parent's shape of things: no such kernel in the trace, or a
    # job that says no state bytes, or no trace at all
    no_call = dict(ctx, reduction={"ops": {"fusion.1": [0.5, 10]},
                                   "programs": red["programs"]})
    assert reader.read(no_call) is None
    assert reader.read(dict(ctx, readings={})) is None
    assert reader.read(dict(ctx, reduction=None)) is None
