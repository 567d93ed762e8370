"""The yardstick's own arithmetic: percentiles, work counts, discovery,
the trace reduction, and the no-chip exit."""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

import harness
import readers
import trace_reduce
import work

from conftest import BENCH, ROOT


def test_p95_counts_from_due_time_and_refusals_miss():
    # 19 answers 10..28 ms after their due time, one refusal: the nearest
    # rank of 0.95 x 20 is the 19th value, the largest answered latency
    lat = [(10 + i) / 1e3 for i in range(19)] + [math.inf]
    assert readers.p95_ms({"infer_latencies_s": lat}) == pytest.approx(28.0)
    # two refusals in twenty: the 19th value is a miss
    lat2 = lat[:18] + [math.inf, math.inf]
    assert readers.p95_ms({"infer_latencies_s": lat2}) == math.inf


def test_latency_is_from_due_time():
    r = harness.Rec("t0000", 0, 1, due=2.0)
    r.sub, r.done = 2.5, 2.75          # sent half a second late
    r.fut = type("F", (), {"exception": lambda self: None})()
    assert r.latency() == pytest.approx(0.75)
    refused = harness.Rec("t0000", 0, 1, due=2.0)
    refused.refused = True
    assert refused.latency() == math.inf


def _schedule(open_mix, seconds=20.0, tenants=8):
    run = harness.ServeRun({}, {"open": open_mix}, 3000000019)
    run.names = [f"t{i:04d}" for i in range(tenants)]
    return run._open_schedule(seconds)


def test_open_loop_schedule():
    recs = _schedule({"rate_rps": 200, "rows": [[1, 3], [32, 1]],
                      "zipf": 1.1})
    due = [r.due for r in recs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    assert len(recs) == pytest.approx(200 * 20, rel=0.1)
    share32 = sum(r.rows == 32 for r in recs) / len(recs)
    assert share32 == pytest.approx(0.25, abs=0.05)
    first = sum(r.tenant == "t0000" for r in recs) / len(recs)
    last = sum(r.tenant == "t0007" for r in recs) / len(recs)
    assert first > 4 * last            # rank 1 vs rank 8 under Zipf(1.1)
    again = _schedule({"rate_rps": 200, "rows": [[1, 3], [32, 1]],
                       "zipf": 1.1})
    assert [(r.due, r.tenant, r.rows) for r in again] == [
        (r.due, r.tenant, r.rows) for r in recs]     # same seed, same work


@pytest.mark.parametrize("name,C,L2,H", [("mnist-cotm", 2000, 1568, 10),
                                         ("kws6-cotm", 2000, 3200, 6)])
def test_work_counts_at_published_widths(name, C, L2, H):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert work.sizes(cfg) == (C, L2, H)
    assert work.infer_ops_per_row(cfg) == 2 * C * L2 + 2 * C * H
    assert work.train_ops_per_row(cfg) == 3 * C * L2 + 2 * C * H
    ce = work.clause_eval(cfg, rows=32, requests=2)
    assert ce["ops"] == 2 * C * L2 * 32
    assert ce["bytes"] == 2 * C * L2 / 8 + 32 * L2 / 8 + 32 * C / 8
    ta = work.ta_update(cfg, rows=64, steps=2, active_share=0.5)
    assert ta["bytes"] == 2 * C * L2 * 2 * 0.5
    assert ta["ops"] == C * L2 * 64 * 0.5
    peak = work.peaks("TPU v5 lite")
    # the MNIST include plane at HBM peak: 392 KB / 819 GB/s
    one = work.clause_eval(cfg, rows=1, requests=1)
    assert work.roofline_s(one, peak) == pytest.approx(
        one["bytes"] / 819e9)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_every_cell_is_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        d = harness.load_cell(w["name"])
        assert d["config"]["name"] == w["config"]
        assert d["end_to_end"] and d["per_layer"]
        for m in d["end_to_end"] + d["per_layer"]:
            assert callable(harness.reader(m["name"]))
    assert set(trace_reduce.stage_patterns()) >= {"clause_eval",
                                                  "ta_update"}


def test_reader_returns_nothing_without_a_trace():
    ctx = {"config": json.loads(
        (BENCH / "configs" / "mnist-cotm.json").read_text()),
        "infer_rows_all": 10, "infer_requests_all": 10, "peaks": None}
    assert readers.clause_eval_roofline(ctx) is None
    assert readers.idle(ctx) is None


def _recorded():
    return json.loads((BENCH / "tests" / "data"
                       / "edge_trace_slice.json").read_text())


def test_trace_reduction_on_a_recorded_trace():
    rec = _recorded()
    events = [tuple(e) for e in rec["events"]]
    got = trace_reduce.reduce(events, rec["lo"], rec["hi"],
                              trace_reduce.stage_patterns())
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    # busy time is the union of the op intervals inside the window
    ops = sorted((max(s, rec["lo"]), min(e, rec["hi"])) for p, ln, n, s, e
                 in events if p.startswith("/device:TPU:")
                 and e > rec["lo"] and s < rec["hi"])
    union, end = 0.0, -1.0
    for s, e in ops:
        if e > end:
            union += e - max(s, end)
            end = e
    assert got["busy_s"] == pytest.approx(union / 1e9)
    # per-name seconds are of leaf operations: a copy-start/done pair or
    # a fusion counts, the control flow around them does not
    leaves = trace_reduce._leaves([(max(s, rec["lo"]), min(e, rec["hi"]), n)
                                   for p, ln, n, s, e in events
                                   if p.startswith("/device:TPU:")
                                   and e > rec["lo"] and s < rec["hi"]])
    top_name, top_s = got["device_ops"][0]
    assert top_s == pytest.approx(sum(e - s for s, e, n in leaves
                                      if n == top_name) / 1e9)
    assert sum(v for _, v in got["device_ops"]) <= got["busy_s"] + 1e-12
    idle = sum(v for _, v in trace_reduce._attribute(
        events, _gaps(events, rec), rec["lo"], rec["hi"]))
    assert idle == pytest.approx(got["window_s"] - union / 1e9)


def _gaps(events, rec):
    dev = trace_reduce._union([(max(s, rec["lo"]), min(e, rec["hi"]))
                               for p, _, _, s, e in events
                               if p.startswith("/device:TPU:")
                               and e > rec["lo"] and s < rec["hi"]])
    gaps, t = [], rec["lo"]
    for s, e in dev:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < rec["hi"]:
        gaps.append((t, rec["hi"]))
    return gaps


def test_innermost_span_attribution():
    spans = [(0, 100, "outer"), (10, 20, "inner"), (30, 40, "inner2")]
    assert trace_reduce._innermost(spans, [5, 15, 25, 35, 150]) == [
        "outer", "inner", "outer", "inner2", None]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "kws6-batch-b32", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_directory_without_the_program_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and bench/: past the chip check (skipped
    here), the run cannot import the program and prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, 'bench'); import harness; "
            "sys.exit(harness.run(harness.parse(['--workload', "
            "'kws6-batch-b32', '--seed', '1', '--seconds', '1']), 0.0, "
            "require_chip=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "repro" in p.stderr
