"""CPU rehearsals of the readers of the program's own spans — run by
hand with the rest of chipbench/tests, not part of tier-1.  Synthetic
records for the arithmetic; the tiny end-to-end rehearsal of
test_chipbench.py, unedited, with the span metrics added to each cell's
list.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps          # noqa: E402
from chipbench.tests import test_chipbench as base         # noqa: E402


def span(name, begin, end, tid=1, seq=0):
    return {"name": name, "t_begin": begin, "t_end": end, "tid": tid,
            "seq": seq}


# one engine iteration [10, 20] on thread 1: an admission [11, 14] with a
# prefill inside it, a decode step [15, 18] tiled by dispatch and
# readback, an emit [18.5, 19.5]; a client's span on thread 2; a queue
# wait recorded by thread 1 that began long before
ITER = span("engine.iteration", 10.0, 20.0)
RECORDS = [
    ITER,
    span("engine.prefill", 11.0, 14.0),
    span("model.prefill", 11.5, 13.0),
    span("model.step", 15.0, 18.0),
    span("model.step.dispatch", 15.0, 16.0),
    span("model.step.readback", 16.0, 18.0),
    span("engine.emit", 18.5, 19.5),
    span("client.wait", 9.0, 30.0, tid=2),
    span("queue.wait", 2.0, 10.5),
]


def test_on_thread_of_keeps_one_thread_and_drops_retroactive_records():
    mine = ps.on_thread_of(RECORDS, "engine.iteration")
    assert [s["name"] for s in mine] == [
        "engine.iteration", "engine.prefill", "model.prefill", "model.step",
        "model.step.dispatch", "model.step.readback", "engine.emit"]
    assert ps.on_thread_of(RECORDS, "no.such.root") == []


def test_self_time_takes_out_what_lies_inside_by_time():
    mine = ps.on_thread_of(RECORDS, "engine.iteration")
    # 10 s minus the union of [11, 14], [15, 18], [18.5, 19.5]; the
    # grandchildren change nothing
    assert ps.self_time(ITER, mine) == pytest.approx(3.0)
    assert ps.self_time(mine[1], mine) == pytest.approx(1.5)
    assert ps.self_time(mine[3], mine) == pytest.approx(0.0)
    # a span that only begins inside is not inside
    late = span("straggler", 19.8, 25.0)
    assert ps.self_time(ITER, ps.on_thread_of(RECORDS + [late],
                                              "engine.iteration")) \
        == pytest.approx(3.0)


def test_innermost_flattens_to_disjoint_segments():
    segs = ps.innermost(ps.on_thread_of(RECORDS, "engine.iteration"))
    assert segs == [
        (10.0, 11.0, "engine.iteration"), (11.0, 11.5, "engine.prefill"),
        (11.5, 13.0, "model.prefill"), (13.0, 14.0, "engine.prefill"),
        (14.0, 15.0, "engine.iteration"),
        (15.0, 16.0, "model.step.dispatch"),
        (16.0, 18.0, "model.step.readback"),
        (18.0, 18.5, "engine.iteration"), (18.5, 19.5, "engine.emit"),
        (19.5, 20.0, "engine.iteration")]
    # a child that outlasts its parent is cut at the parent's end
    assert ps.innermost([span("a", 0.0, 1.0), span("b", 0.5, 2.0)]) == [
        (0.0, 0.5, "a"), (0.5, 1.0, "b")]


def test_idle_by_span_gives_each_gap_to_the_innermost_span():
    mine = ps.on_thread_of(RECORDS, "engine.iteration")
    offset_ns = 1_000_000_000_000      # trace clock = host clock + 1000 s

    def gap(a, b):                     # host seconds -> trace ns
        return (int(a * 1e9) + offset_ns, int(b * 1e9) + offset_ns)

    table = ps.idle_by_span(
        [gap(8.0, 10.5),               # 2 s outside, 0.5 s iteration
         gap(12.0, 13.5),              # 1 s model.prefill, 0.5 s prefill
         gap(15.5, 16.5),              # 0.5 s dispatch, 0.5 s readback
         gap(19.0, 21.0)],             # 0.5 emit, 0.5 iteration, 1 outside
        offset_ns, mine)
    assert table == pytest.approx({
        "outside": 3.0, "engine.iteration": 1.0, "engine.prefill": 0.5,
        "model.prefill": 1.0, "model.step": 0.0,
        "model.step.dispatch": 0.5, "model.step.readback": 0.5,
        "engine.emit": 0.5})
    assert sum(table.values()) == pytest.approx(2.5 + 1.5 + 1.0 + 2.0)
    # the offset applied the other way round would put every gap
    # 2000 s from every span
    wrong = ps.idle_by_span([gap(12.0, 13.5)], -offset_ns, mine)
    assert wrong["outside"] == pytest.approx(1.5)
    # no span at all: everything is outside
    assert ps.idle_by_span([gap(1.0, 2.0)], offset_ns, []) == {
        "outside": pytest.approx(1.0)}


def test_idle_pct_partitions_the_idle_share(monkeypatch):
    from mxnet_tpu import tracing
    monkeypatch.setattr(tracing, "spans", lambda: list(RECORDS))
    red = {"gaps": [(8_000_000_000, 10_500_000_000),
                    (12_000_000_000, 13_500_000_000),
                    (15_500_000_000, 16_500_000_000),
                    (19_000_000_000, 21_000_000_000)],
           "offset_ns": 0, "window_s": 20.0}
    ctx = {"reduction": red}
    shares = {g: ps.idle_pct(ctx, g)
              for g in ("decode_call", "admission", "engine_host")}
    assert shares == pytest.approx({"decode_call": 5.0, "admission": 7.5,
                                    "engine_host": 22.5})
    assert sum(shares.values()) == pytest.approx(100 * 7.0 / 20.0)
    # a program that has no span inside its iteration cannot be split
    monkeypatch.setattr(tracing, "spans", lambda: [ITER])
    assert ps.idle_pct({"reduction": red}, "engine_host") is None
    assert ps.idle_pct({"reduction": None}, "engine_host") is None


def test_resident_says_what_it_found_and_what_the_ring_lost(
        monkeypatch, capsys):
    from mxnet_tpu import tracing
    ring = [span("step.place", float(t), t + 0.5, seq=40 + i)
            for i, t in enumerate(range(50, 60))]
    monkeypatch.setattr(tracing, "spans", lambda: list(ring))
    found = ps.resident("step.place", 45.0, 55.0)
    assert [s["t_begin"] for s in found] == [50.0, 51.0, 52.0, 53.0, 54.0,
                                             55.0]
    err = capsys.readouterr().err
    assert "6 x step.place" in err and "10 resident" in err
    assert "overwritten up to 5.500 s after the start" in err
    assert ps.resident("step.place", 45.0, 55.2, by="t_end")[-1][
        "t_begin"] == 54.0
    capsys.readouterr()
    # a ring that never wrapped has lost nothing
    for i, s in enumerate(ring):
        s["seq"] = i
    assert ps.resident("no.such", 45.0, 55.0) == []
    assert "overwritten" not in capsys.readouterr().err
    assert ps.mean_ms({"t_proc": 40.0, "end_to_end": {"setup_s": 5.0},
                       "seconds": 10.0}, "step.place") == pytest.approx(500)
    assert ps.mean_ms({"t_proc": 0.0, "end_to_end": {"setup_s": 5.0},
                       "seconds": 10.0}, "step.place") is None


SPAN_METRICS = {
    "tiny_bert.train": ["step_place_ms", "step_enqueue_ms"],
    "tiny_gpt.serve": ["queue_wait_p95_ms", "admission_ms",
                       "decode_dispatch_ms", "engine_host_ms",
                       "idle_pct.decode_call", "idle_pct.admission",
                       "idle_pct.engine_host"],
}


def test_benchmark_json_lists_the_span_metrics_for_their_cells():
    by_name = {m["name"]: m for m in base.BENCH["per_layer"]}
    cells = {"tiny_bert.train": "bert_large.train_mlm512",
             "tiny_gpt.serve": "gpt2_774m.serve_doc"}
    for tiny, names in SPAN_METRICS.items():
        for name in names:
            assert by_name[name]["workloads"] == [cells[tiny]], name
            assert by_name[name]["source"] == "program_span"


@pytest.mark.parametrize("cell_name", sorted(SPAN_METRICS))
def test_the_tiny_rehearsal_reads_every_span_metric(
        tmp_path, monkeypatch, capsys, cell_name):
    """test_chipbench's own end-to-end rehearsal, with the span metrics
    added to the cell's list: every one reads a value (the rehearsal
    holds the line's metrics to the list)."""
    metrics, config, cell = base.TINY[cell_name]
    monkeypatch.setitem(base.TINY, cell_name,
                        (metrics + SPAN_METRICS[cell_name], config, cell))
    base.test_job_end_to_end_at_a_tiny_size(tmp_path, monkeypatch,
                                            cell_name, 1)
    assert '"idle_by_span": {' in capsys.readouterr().err
