"""``rows_read_pct`` on synthetic records, beside the other span
readers' cases — run by hand with the rest of chipbench/tests."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run                                   # noqa: E402

reader = run._load_module(os.path.join(ROOT, "chipbench", "metrics",
                                       "rows_read_pct.py"))


def dispatch(begin, **attrs):
    return {"name": "model.step.dispatch", "t_begin": begin,
            "t_end": begin + 0.004, "tid": 1, "seq": 0, "attrs": attrs}


@pytest.mark.parametrize("blocks,want", [
    ([(512, 512)], 100.0),
    ([(64, 512), (64, 512)], 12.5),
    ([(100, 128), (300, 512)], 62.5),        # summed, not averaged
])
def test_share_of_the_blocks_read(blocks, want):
    spans = [dispatch(float(i), slots=64, ahead=1, row_blocks=r,
                      row_blocks_all=a) for i, (r, a) in enumerate(blocks)]
    assert reader.share_read(spans) == pytest.approx(want)


def test_a_dense_step_reads_nothing_here():
    # the GPT family and the parent commit: spans with the other
    # attributes, with none, or with no attrs at all
    assert reader.share_read([dispatch(1.0, slots=8, ahead=1),
                              dispatch(2.0)]) is None
    bare = dispatch(3.0)
    del bare["attrs"]
    assert reader.share_read([bare]) is None
    assert reader.share_read([]) is None
    # one of the two alone is no reading
    assert reader.share_read([dispatch(4.0, row_blocks=3)]) is None


def test_read_takes_the_dispatches_that_began_in_the_window(monkeypatch):
    from mxnet_tpu import tracing
    ring = [dispatch(9.0, row_blocks=512, row_blocks_all=512),   # before
            dispatch(10.5, row_blocks=100, row_blocks_all=512),
            dispatch(19.9, row_blocks=156, row_blocks_all=512),
            dispatch(20.5, row_blocks=512, row_blocks_all=512),  # after
            dict(dispatch(11.5, row_blocks=512, row_blocks_all=512),
                 name="model.step.readback")]
    monkeypatch.setattr(tracing, "spans", lambda: list(ring))
    ctx = {"t_proc": 4.0, "end_to_end": {"setup_s": 6.0}, "seconds": 10.0}
    assert reader.read(ctx) == pytest.approx(25.0)
    monkeypatch.setattr(tracing, "spans",
                        lambda: [dispatch(11.0, ahead=1)])
    assert reader.read(ctx) is None


def test_benchmark_json_lists_it_for_the_hybrid_cell():
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "rows_read_pct")
    assert entry == {
        "name": "rows_read_pct", "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": ["phi4_mini_flash.serve_reason"]}
