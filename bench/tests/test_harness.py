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
import kinds
import readers
import trace_reduce
import work

from conftest import BENCH, ROOT, TINY_TRAFFIC, tiny_config


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


@pytest.mark.parametrize("name,C,L2,H,infer,train", [
    ("mnist-cotm", 2000, 1568, 10, 6_312_000, 9_448_000),
    ("kws6-cotm", 2000, 3200, 6, 12_824_000, 19_224_000)])
def test_work_counts_at_published_widths(name, C, L2, H, infer, train):
    """The coalesced kind counts the closed forms of ``kinds/coalesced.py``
    at the published widths, whatever the engine pads them to."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    kind = kinds.of(cfg)
    assert kind is kinds.of({"kind": "coalesced"})
    assert kind.sizes(cfg) == (C, L2, H)
    assert kind.infer_ops_per_row(cfg) == 2 * C * L2 + 2 * C * H == infer
    assert kind.train_ops_per_row(cfg) == 3 * C * L2 + 2 * C * H == train
    ce = kind.clause_eval(cfg, rows=32, requests=2)
    assert ce["ops"] == 2 * C * L2 * 32
    assert ce["bytes"] == 2 * C * L2 / 8 + 32 * L2 / 8 + 32 * C / 8
    ta = kind.ta_update(cfg, rows=64, steps=2, active_share=0.5)
    assert ta["bytes"] == 2 * C * L2 * 2 * 0.5
    assert ta["ops"] == C * L2 * 64 * 0.5
    peak = work.peaks("TPU v5 lite")
    # the MNIST include plane at HBM peak: 392 KB / 819 GB/s
    one = kind.clause_eval(cfg, rows=1, requests=1)
    assert work.roofline_s(one, peak) == pytest.approx(
        one["bytes"] / 819e9)


def test_shares_read_the_kind_modules_work():
    """``infer_mfu``, ``train_mfu`` and both rooflines, from a context
    with peaks and stage seconds, are the kind's counts over them."""
    cfg = json.loads((BENCH / "configs" / "kws6-cotm.json").read_text())
    kind = kinds.of(cfg)
    peak = work.peaks("TPU v5 lite")
    ctx = {"config": cfg, "peaks": peak, "infer_rows": 64_000,
           "infer_span_s": 2.0, "infer_rows_all": 70_000,
           "infer_requests_all": 2_000, "train_rows": 6_400,
           "train_span_s": 4.0, "train_steps": 200, "active_share": 0.25,
           "trace": {"stage_s": {"clause_eval": 0.5, "ta_update": 3.0},
                     "devices": 1, "busy_s": 3.5, "window_s": 4.0}}
    assert harness.reader("infer_mfu")(ctx) == pytest.approx(
        100 * kind.infer_ops_per_row(cfg) * 32_000 / 393e12)
    assert harness.reader("train_mfu")(ctx) == pytest.approx(
        100 * kind.train_ops_per_row(cfg) * 1_600 / 393e12)
    assert harness.reader("clause_eval_roofline.infer_rows")(ctx) == (
        pytest.approx(100 * work.roofline_s(
            kind.clause_eval(cfg, 70_000, 2_000), peak) / 0.5))
    assert harness.reader("ta_update_roofline")(ctx) == pytest.approx(
        100 * work.roofline_s(kind.ta_update(cfg, 6_400, 200, 0.25),
                              peak) / 3.0)


@pytest.mark.parametrize("kind", [None, "warp"])
def test_a_configuration_needs_a_kind_with_a_module(tmp_path, kind):
    """No ``"kind"``, or a kind with no ``kinds/<kind>.py``: ``load_cell``
    fails naming the file it looked for, and never falls back.  The
    configuration is read from ``root``; the kind module, being code, is
    looked for in the package."""
    cfg = tiny_config()
    del cfg["kind"]
    if kind:
        cfg["kind"] = kind
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny"}]
    bench["workloads"] = [{"name": "kws6-batch-b32", "config": "tiny",
                           "traffic": "batch-b32-closed", "chips": 1,
                           "why": "tiny"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(LookupError) as e:
        harness.load_cell("kws6-batch-b32", tmp_path)
    assert str(BENCH / "kinds" / f"{kind or '<kind>'}.py") in str(e.value)


NEW_KIND = '''"""A kind of its own name that runs as the coalesced kind."""
from kinds.coalesced import *  # noqa: F401,F403
'''


def _files(root) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("cell,rate", [("kws6-batch-b32", "infer_rows_per_s"),
                                       ("cotm-fit-b32", "train_rows_per_s")])
def test_a_new_kind_joins_by_new_files_alone(tmp_path, cell, rate):
    """A copy of the benchmark gains a kind module, a configuration of that
    kind, a traffic file and its entries: a tiny cell of it runs end to end
    through the copy's own harness, correct, and no file of the copy that
    was there before changed (``BENCHMARK.json`` only gained entries)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    before = _files(root)
    old = json.loads(before["BENCHMARK.json"])

    cfg = dict(tiny_config(), name="tiny-relabelled", kind="relabelled")
    (root / "bench" / "kinds" / "relabelled.py").write_text(NEW_KIND)
    (root / "bench" / "configs" / "tiny-relabelled.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-relabelled.json").write_text(
        json.dumps(TINY_TRAFFIC[cell]))
    bench = json.loads(json.dumps(old))
    bench["configs"].append({"name": "tiny-relabelled", "source": "tests",
                             "file": "bench/configs/tiny-relabelled.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": "relabelled", "config":
                               "tiny-relabelled", "traffic": "tiny-relabelled",
                               "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] == rate:
            m["workloads"].append("relabelled")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    code = ("import sys; sys.path.insert(0, 'bench'); import harness; "
            "sys.exit(harness.run(harness.parse(['--workload', 'relabelled', "
            "'--seed', '3000000019', '--seconds', '1.5']), 0.0, "
            "require_chip=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {rate, "setup_s"}
    assert all(c["value"] == 0 for c in res["compared"].values())

    after = _files(root)
    assert {k: v for k, v in after.items() if k in before
            and k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    new = json.loads(after["BENCHMARK.json"])

    def without_cell(e):
        if "workloads" not in e:
            return e
        return dict(e, workloads=[w for w in e["workloads"]
                                  if w != "relabelled"])

    for key, entries in old.items():
        if isinstance(entries, list):
            assert [without_cell(e) for e in new[key][:len(entries)]] == \
                entries
        else:
            assert new[key] == entries


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_every_cell_is_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        d = harness.load_cell(w["name"])
        assert d["config"]["name"] == w["config"]
        kind = d["config"]["kind"]
        assert kinds.of(d["config"]).__name__ == f"kinds.{kind}"
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
