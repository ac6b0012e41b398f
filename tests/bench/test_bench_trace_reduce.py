"""The trace reduction: nesting, busy union and idle share, attribution,
on a synthetic trace with known intervals and on a cut of a recorded
chip trace of the hyena-153m train step."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).with_name("data") / "train_step_cut.trace.json.gz"


def _meta():
    return [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]


def _op(ts, dur, name, cat="loop fusion", tf_op="", source=""):
    return {"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
            "name": name, "args": {"hlo_category": cat, "tf_op": tf_op,
                                   "source": source}}


def _span(ts, dur, name):
    return {"ph": "X", "pid": 9, "tid": 1, "ts": ts, "dur": dur,
            "name": name}


def synthetic():
    # times in microseconds: a while [0, 40] holding two overlapping ops
    # and a third; one op outside; an untyped container [60, 80] holding
    # one op; the window is [0, 100]
    return {"traceEvents": _meta() + [
        _op(0, 40, "while.1", cat="while"),
        _op(0, 10, "fusion.1", tf_op="jit(step)/while/body/jit(fft):"),
        _op(5, 10, "fusion.2", source="src/repro/core/fftconv.py:88"),
        _op(20, 10, "fusion.3", tf_op="jit(step)/while/body/dot"),
        _op(45, 5, "all-to-all.7", cat="collective"),
        _op(60, 20, "call.2", cat="custom fusion"),
        _op(62, 8, "fusion.4"),
        {"ph": "X", "pid": 3, "tid": 2, "ts": 0, "dur": 90,
         "name": "jit_step(123)", "args": {}},
        _span(0, 100, "bench.window"),
        _span(40, 5, "bench.sync"),
        _span(80, 20, "bench.data"),
    ]}


def test_containers_are_not_summed():
    r = TR.reduce_trace(synthetic())
    names = sorted(o.name for o in r.ops)
    assert names == ["all-to-all.7", "fusion.1", "fusion.2", "fusion.3",
                     "fusion.4"]  # while.1 and call.2 enclose other ops


def test_busy_union_and_idle_share():
    r = TR.reduce_trace(synthetic())
    # union: [0, 15] + [20, 30] + [45, 50] + [62, 70] = 15 + 10 + 5 + 8
    assert r.busy_s == pytest.approx(38e-6)
    assert r.window_s == pytest.approx(100e-6)
    assert r.idle_share == pytest.approx(0.62)


def test_attribution_by_source_tf_op_and_name():
    r = TR.reduce_trace(synthetic())
    layer = {o.name: o.layer for o in r.ops}
    assert layer == {"fusion.1": "long conv", "fusion.2": "long conv",
                     "fusion.3": "other", "all-to-all.7": "collectives",
                     "fusion.4": "other"}
    assert r.layer_time("long conv") == pytest.approx(20e-6)
    assert [p.name for p in r.programs] == ["train_step"]
    assert r.layer_time("long conv", within="train_step") == pytest.approx(
        20e-6)


def test_breakdown_labels_gaps_by_the_open_span():
    b = TR.breakdown(TR.reduce_trace(synthetic()))
    gaps = dict((k, round(v * 1e6, 6)) for k, v in b["idle_gaps"])
    # gaps: [15, 20], [30, 45], [50, 62], [70, 100]
    assert gaps["bench.data"] == 30.0  # [70, 100] lies under bench.data
    assert gaps["bench.sync"] == 15.0  # [30, 45] overlaps bench.sync
    assert len(b["idle_gaps"]) == 4
    top = b["device_ops"][0]
    assert top[0].startswith("other: fusion.3") or top[0].startswith(
        "long conv: fusion")


def test_recorded_trace_cut():
    trace = TR.load_events(DATA)
    r = TR.reduce_trace(trace)
    raw = [e for e in trace["traceEvents"]
           if e.get("ph") == "X" and e.get("tid") == 3]
    whiles = [e for e in raw if e["args"].get("hlo_category") == "while"]
    assert len(whiles) == 1
    assert all(not o.name.startswith("while") for o in r.ops)
    assert len(r.ops) == len(raw) - 1
    total = sum(o.dur for o in r.ops)
    # the scan's while alone lasts 0.43 s: summing it would dwarf the cut
    assert total < 0.05 < whiles[0]["dur"] * 1e-6
    assert r.busy_s <= total + 1e-12
    assert 0.0 < r.idle_share < 1.0
    want = sum(1 for e in raw if e["args"].get("hlo_category") != "while"
               and ("fft" in e["args"].get("tf_op", "")
                    or e["args"].get("source", "").startswith(
                        "src/repro/core/fftconv.py")))
    assert want > 50
    assert sum(1 for o in r.ops if o.layer == "long conv") == want


def test_missing_window_span_raises():
    t = synthetic()
    t["traceEvents"] = [e for e in t["traceEvents"]
                        if e.get("name") != "bench.window"]
    with pytest.raises(ValueError):
        TR.reduce_trace(t)
