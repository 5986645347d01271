"""``loop_passes_per_token`` on recorded spans and
``decode_attn_roofline_pct`` on a recorded trace reduction, beside the
other readers' cases — run by hand with the rest of chipbench/tests."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402

CELL = "ouro_2_6b.serve_math"
passes = run._load_module(os.path.join(
    ROOT, "chipbench", "metrics", "loop_passes_per_token.py"))
roofline = run._load_module(os.path.join(
    ROOT, "chipbench", "metrics", "decode_attn_roofline_pct.py"))


def dispatch(begin, **attrs):
    return {"name": "model.step.dispatch", "t_begin": begin,
            "t_end": begin + 0.004, "tid": 1, "seq": 0, "attrs": attrs}


def test_mean_loop_steps_over_the_launches_that_say_them():
    spans = [dispatch(1.0, family="loop", loop_steps=4, layer_passes=192),
             dispatch(2.0, family="loop", loop_steps=4, layer_passes=192),
             dispatch(3.0, family="loop", loop_steps=2, layer_passes=96)]
    assert passes.mean_loop_steps(spans[:2]) == 4.0
    # a change that lets a step's tokens leave early says fewer
    assert passes.mean_loop_steps(spans) == pytest.approx(10 / 3)


def test_a_family_without_a_loop_and_the_parent_commit_read_nothing():
    assert passes.mean_loop_steps([dispatch(1.0, slots=8, ahead=1),
                                   dispatch(2.0)]) is None
    bare = dispatch(3.0)
    del bare["attrs"]
    assert passes.mean_loop_steps([bare]) is None
    assert passes.mean_loop_steps([]) is None


def test_read_takes_the_dispatches_that_began_in_the_window(monkeypatch):
    from mxnet_tpu import tracing
    ring = [dispatch(9.0, loop_steps=1),                         # before
            dispatch(10.5, loop_steps=4), dispatch(19.9, loop_steps=4),
            dispatch(20.5, loop_steps=1),                        # after
            dict(dispatch(11.5, loop_steps=1), name="model.prefill")]
    monkeypatch.setattr(tracing, "spans", lambda: list(ring))
    ctx = {"t_proc": 4.0, "end_to_end": {"setup_s": 6.0}, "seconds": 10.0}
    assert passes.read(ctx) == 4.0
    monkeypatch.setattr(tracing, "spans",
                        lambda: [dispatch(11.0, ahead=1)])
    assert passes.read(ctx) is None


def reduction(**ops):
    """A recorded reduction: 100 decode programs in the stretch, the
    kernels' device seconds as given."""
    return {"ops": {name: [secs, 19200.0] for name, secs in ops.items()},
            "programs": {"jit__step": [4.0, 100.0],
                         "jit__prefill": [0.5, 2.0]}}


PEAKS = {"hbm_bytes_per_s": 819e9}


def test_the_kernels_share_of_its_roofline_by_hand():
    # 4.6 GB of live K/V a step, 100 steps, at 819 GB/s: 0.5617 s at
    # the least; the kernel's calls took 0.9 s
    ctx = {"reduction": reduction(**{
        "ragged_attention.3[tpu_custom_call]": 0.9,
        "write_columns.8[tpu_custom_call]": 0.2, "fusion.12": 2.5}),
        "readings": {"attn_bytes": 4.6e9}, "peaks": PEAKS}
    assert roofline.read(ctx) == pytest.approx(
        100.0 * (100 * 4.6e9 / 819e9) / 0.9)
    assert 0 < roofline.read(ctx) < 100
    # several numbered calls of the kernel sum
    ctx["reduction"] = reduction(**{
        "ragged_attention[tpu_custom_call]": 0.4,
        "ragged_attention.7[tpu_custom_call]": 0.5})
    assert roofline.read(ctx) == pytest.approx(
        100.0 * (100 * 4.6e9 / 819e9) / 0.9)


@pytest.mark.parametrize("ctx", [
    {"reduction": None, "readings": {"attn_bytes": 1e9}},
    # a job that says no bytes (the other serving jobs)
    {"reduction": reduction(**{"ragged_attention[tpu_custom_call]": 0.9}),
     "readings": {}},
    # a trace without the kernel under this name (the parent commit
    # calls it ``_step``), or without a decode program
    {"reduction": reduction(**{"_step.4[tpu_custom_call]": 0.9}),
     "readings": {"attn_bytes": 1e9}},
    {"reduction": {"ops": {"ragged_attention[tpu_custom_call]": [0.9, 1]},
                   "programs": {"jit__prefill": [0.5, 2.0]}},
     "readings": {"attn_bytes": 1e9}},
])
def test_nothing_to_read_is_none_and_does_not_raise(ctx):
    assert roofline.read(dict(ctx, peaks=PEAKS)) is None


@pytest.mark.parametrize("reader,name,better", [
    (passes, "loop_passes_per_token", "lower"),
    (roofline, "decode_attn_roofline_pct", "higher")])
def test_benchmark_json_lists_them_for_the_loop_cell(reader, name, better):
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": reader.UNIT, "better": better,
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": [CELL]}
