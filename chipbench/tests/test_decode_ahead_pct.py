"""``decode_ahead_pct`` on synthetic records, beside the other span
readers' cases (test_program_spans.py) — run by hand with the rest of
chipbench/tests."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402

reader = run._load_module(os.path.join(ROOT, "chipbench", "metrics",
                                       "decode_ahead_pct.py"))


def dispatch(begin, **attrs):
    return {"name": "model.step.dispatch", "t_begin": begin,
            "t_end": begin + 0.004, "tid": 1, "seq": 0, "attrs": attrs}


@pytest.mark.parametrize("aheads,want", [
    ([1, 1, 1, 1], 100.0),
    ([0, 0, 0], 0.0),
    ([0, 1, 1, 1, 0, 1, 1, 1], 75.0),
])
def test_share_of_the_dispatches_launched_ahead(aheads, want):
    spans = [dispatch(float(i), slots=64, ahead=a)
             for i, a in enumerate(aheads)]
    assert reader.share_ahead(spans) == pytest.approx(want)


def test_a_program_without_the_attribute_reads_nothing():
    # the parent commit's dispatch span has no attributes at all, and
    # an older record may lack the key
    assert reader.share_ahead([dispatch(1.0), dispatch(2.0)]) is None
    bare = dispatch(3.0)
    del bare["attrs"]
    assert reader.share_ahead([bare]) is None
    assert reader.share_ahead([]) is None


def test_read_takes_the_dispatches_that_began_in_the_window(monkeypatch):
    from mxnet_tpu import tracing
    ring = [dispatch(9.0, ahead=0),              # before the window
            dispatch(10.5, ahead=0), dispatch(11.0, ahead=1),
            dispatch(12.0, ahead=1), dispatch(19.9, ahead=1),
            dispatch(20.5, ahead=0),             # after it
            dict(dispatch(11.5, ahead=0), name="model.step.readback")]
    monkeypatch.setattr(tracing, "spans", lambda: list(ring))
    ctx = {"t_proc": 4.0, "end_to_end": {"setup_s": 6.0}, "seconds": 10.0}
    assert reader.read(ctx) == pytest.approx(75.0)
    monkeypatch.setattr(tracing, "spans", lambda: [dispatch(11.0)])
    assert reader.read(ctx) is None


def test_benchmark_json_lists_it_for_the_serving_cells():
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "decode_ahead_pct")
    assert entry == {
        "name": "decode_ahead_pct", "unit": reader.UNIT, "better": "higher",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": ["gpt2_774m.serve_doc",
                      "phi4_mini_flash.serve_reason"]}
    assert bench["per_layer"][-1] is entry
