"""The program-span reduction (``span_reduce.py``) and the traced run
that reads it (``span_run.py``), on hand-built events, the recorded
trace slice and, at a tiny size, on the CPU."""
from __future__ import annotations

import io
import json

import pytest

import span_reduce
import span_run
import trace_reduce

from conftest import BENCH, TINY_TRAFFIC

HOST, DEV = "/host:CPU", "/device:TPU:0"


def _ev(plane, name, s, e, line="python"):
    return (plane, line if plane == HOST else "XLA Ops", name, float(s),
            float(e))


def test_recorded_slice_reduces_as_before():
    """``trace_reduce.reduce`` of the recorded slice, pinned: the span
    reduction is added beside it and changes none of its numbers."""
    rec = json.loads((BENCH / "tests" / "data"
                      / "edge_trace_slice.json").read_text())
    got = trace_reduce.reduce([tuple(e) for e in rec["events"]], rec["lo"],
                              rec["hi"], trace_reduce.stage_patterns())
    assert got["busy_s"] == 0.000970829
    assert got["stage_s"] == {"clause_eval": 0, "ta_update": 0}
    assert got["device_ops"] == [
        ("copy", 0.0007181090000000002), ("class_sum", 0.000139651),
        ("copy-done", 5.173299999999998e-05), ("fusion", 3.2731e-05),
        ("constant_dynamic-slice_fusion", 1.3902000000000002e-05),
        ("dynamic-update-slice", 4.31e-06),
        ("and_reduce_fusion", 2.694e-06), ("dynamic_slice", 2.229e-06),
        ("copy_bitcast_fusion", 1.933e-06),
        ("broadcast_select_fusion", 1.0969999999999998e-06)]
    assert got["idle_gaps"] == [
        ("PjitFunction(_squeeze)", 0.00913233),
        ("PjitFunction(squeeze)", 0.005252888),
        ("DevicePut", 0.0037161279999999982), ("ReadSyncFlag", 0.002596068),
        ("host idle", 0.002116642),
        ("PjitFunction(dynamic_slice)", 0.0020240989999999997),
        ("CompleteCallbacks", 0.001299469),
        ("PjitFunction(broadcast_in_dim)", 0.000998986),
        ("DoEnqueueProgram", 0.000940499),
        ("PjitFunction(convert_element_type)", 0.000461449)]
    # the slice predates the program's spans: every gap is outside them
    assert span_reduce.spans(
        [tuple(e) for e in rec["events"]], rec["lo"], rec["hi"]) == {}
    idle = span_reduce.idle_spans([tuple(e) for e in rec["events"]],
                                  rec["lo"], rec["hi"])
    assert [n for n, _ in idle] == [span_reduce.OUTSIDE]
    assert idle[0][1] == pytest.approx(got["window_s"] - got["busy_s"])


def _nested():
    # a cycle [0, 100) holding a form [5, 10), two encodes and a launch
    # that holds a fetch; a submit on another thread; JAX's own events
    # inside the spans; device busy [20, 30) and [60, 70) of [0, 100)
    return [
        _ev(HOST, "tm.sched.cycle", 0, 100),
        _ev(HOST, "tm.sched.form", 5, 10),
        _ev(HOST, "tm.server.encode", 10, 20),
        _ev(HOST, "PjitFunction(concatenate)", 12, 18),
        _ev(HOST, "tm.server.encode", 30, 50),
        _ev(HOST, "tm.server.launch", 50, 80),
        _ev(HOST, "tm.server.fetch", 55, 75),
        _ev(HOST, "tm.sched.submit", 0, 95, line="client"),
        _ev(HOST, "bench.window", 0, 100),
        _ev(DEV, "packed_clause_eval_mxu", 20, 30),
        _ev(DEV, "class_sum", 60, 70),
    ]


def test_self_time_leaves_out_nested_spans():
    got = span_reduce.spans(_nested(), 0, 100)
    assert got["tm.sched.cycle"] == {"count": 1, "total_s": 100e-9,
                                     "self_s": pytest.approx(35e-9)}
    assert got["tm.server.encode"]["count"] == 2
    assert got["tm.server.encode"]["total_s"] == pytest.approx(30e-9)
    # JAX's events are not spans of the program: no self time lost
    assert got["tm.server.encode"]["self_s"] == pytest.approx(30e-9)
    assert got["tm.server.launch"]["self_s"] == pytest.approx(10e-9)
    assert got["tm.server.fetch"]["self_s"] == pytest.approx(20e-9)
    assert "bench.window" not in got
    # a span counts in the window it starts in
    late = span_reduce.spans(_nested(), 40, 100)
    assert set(late) == {"tm.server.launch", "tm.server.fetch"}


def test_idle_gaps_go_to_the_innermost_span_of_the_busiest_line():
    # gaps [0, 20) mid 10 -> encode (its start), [30, 60) mid 45 ->
    # encode, [70, 100) mid 85 -> cycle; the client line's submit covers
    # them all but holds less span time than the driver's line
    got = dict(span_reduce.idle_spans(_nested(), 0, 100))
    assert got == pytest.approx({"tm.server.encode": 50e-9,
                                 "tm.sched.cycle": 30e-9})
    # a line with more span time comes first: without the cycle, the
    # client's submit outweighs the driver's spans and takes every gap
    no_cycle = [e for e in _nested() if e[2] != "tm.sched.cycle"]
    got = dict(span_reduce.idle_spans(no_cycle, 0, 100))
    assert got == pytest.approx({"tm.sched.submit": 80e-9})
    # a gap that the first line has no span at goes to the next line's
    short = [e if e[2] != "tm.sched.submit" else _ev(HOST, e[2], 80, 95,
                                                      line="client")
             for e in no_cycle]
    got = dict(span_reduce.idle_spans(short, 0, 100))
    assert got == pytest.approx({"tm.server.encode": 50e-9,
                                 "tm.sched.submit": 30e-9})
    # and where no line has one, the gap is outside spans
    driver_only = [e for e in short if e[1] != "client"]
    got = dict(span_reduce.idle_spans(driver_only, 0, 100))
    assert got == pytest.approx({"tm.server.encode": 50e-9,
                                 span_reduce.OUTSIDE: 30e-9})


def test_readers_return_nothing_without_spans_or_counters():
    for ctx in ({}, {"spans": {}, "counters": {}},
                {"counters": {"infer_formed": 0, "infer_queue_wait_s": 0.0}},
                {"counters": {"launches": 3}},
                {"spans": {"tm.fit.bind": {"count": 1, "total_s": 1.0,
                                           "self_s": 1.0}}}):
        assert all(f(ctx) is None for f in span_reduce.READERS.values())
    spans = span_reduce.spans(_nested(), 0, 100)
    ctx = {"spans": spans, "counters": {"infer_formed": 4,
                                        "infer_queue_wait_s": 0.002}}
    assert span_reduce.queue_wait_ms(ctx) == pytest.approx(0.5)
    assert span_reduce.encode_ms(ctx) == pytest.approx(15e-6)
    assert span_reduce.launch_ms(ctx) == pytest.approx(10e-6)
    fit = {"spans": {n: {"count": c, "total_s": t, "self_s": t} for n, c, t
                     in (("tm.fit.bind", 2, 0.2), ("tm.fit.plan", 2, 0.04),
                         ("tm.fit.epoch", 2, 6.0))}}
    assert span_reduce.epoch_prep_ms(fit) == pytest.approx(120.0)


@pytest.mark.parametrize("name", ["kws6-batch-b32", "cotm-fit-b32"])
def test_traced_run_reads_the_programs_spans(tiny_root, name, capsys):
    out = io.StringIO()
    rc = span_run.main(["--workload", name, "--seed", "2200000123",
                        "--seconds", "2"], root=tiny_root,
                       require_chip=False, out=out, err=io.StringIO())
    assert rc == 0
    assert json.loads(out.getvalue().splitlines()[-1])["correct"]
    spans = json.loads(capsys.readouterr().out.splitlines()[-1])
    got = spans["metrics"]
    if "fit" in TINY_TRAFFIC[name]:
        assert got["epoch_prep_ms.train_rows"] > 0
        assert spans["spans"]["tm.fit.epoch"]["count"] >= 1
    else:
        for m in ("queue_wait_ms.infer_rows", "encode_ms.infer_rows",
                  "launch_ms.infer_rows"):
            assert got[m] > 0, m
        assert spans["spans"]["tm.sched.cycle"]["count"] >= 1
    assert spans["rate"] > 0
