"""The ``convtm-fit-b32`` cell and the conv kind (``kinds/conv.py``,
``kinds/conv_ref.py``), on the CPU at a tiny conv configuration, and its
work counts and metric readers at the MNIST geometry.

* the kind keeps the contract and counts its closed forms;
* the cell reports ``train_rows_per_s``, ``setup_s`` and four
  per-layer metrics (three shared with the flat fit cell, one of its
  own), each read from the conv kind's work, and each reader returns
  nothing where the run has nothing to read;
* a sound run compares 0 with the reference, the 8-bit control and a
  broken training step do not.
"""
from __future__ import annotations

import io
import json

import jax
import numpy as np
import pytest

import harness
import kinds
import readers  # noqa: F401
import trace_reduce
import work

from conftest import BENCH, ROOT

CELL = "convtm-fit-b32"
METRICS = ("train_mfu", "ta_update_roofline", "device_idle.train_rows",
           "clause_eval_roofline.conv_train_rows")


def tiny_conv_config() -> dict:
    """``mnist-convtm`` cut to 10x10 images and 4x4 windows (49 patches
    of 28 features), 40 clauses, 4 classes, with the layout the
    program's ``tile_for`` gives that size."""
    cfg = json.loads((BENCH / "configs" / "mnist-convtm.json").read_text())
    cfg.update(name="tiny-conv", img_h=10, img_w=10, patch=4, clauses=40,
               classes=4, T=15, s=3.9)
    cfg["engine"] = dict(literal_columns=128, selection_rows=128,
                         classes_padded=8, negated_literal_column=64,
                         ta_row_stride=256, skip_group_rows=128,
                         draw_lanes=8192, patch_slots=49)
    cfg["dataset"].update(motif_size=3, strokes=2, stroke_length=3)
    return cfg


@pytest.fixture(scope="module")
def conv_root(tmp_path_factory):
    """A checkout-shaped directory with the real metrics and the conv
    cell at the tiny conv configuration."""
    root = tmp_path_factory.mktemp("conv-checkout")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "configs" / "tiny-conv.json").write_text(
        json.dumps(tiny_conv_config()))
    (root / "bench" / "traffic" / "tiny-fit.json").write_text(json.dumps(
        {"programs": "paper_init", "fit": {"rows": 64, "batch": 8},
         "check": {"control": {"rand_bits": 8}}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-conv", "source": "tests",
                         "file": "bench/configs/tiny-conv.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-conv",
                           "traffic": "tiny-fit", "chips": 1,
                           "why": "tiny"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root, seed=7, seconds=1.5):
    out, err = io.StringIO(), io.StringIO()
    args = harness.parse(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"])
    assert harness.run(args, 0.0, root=root, require_chip=False, out=out,
                       err=err) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _mnist() -> dict:
    return json.loads((BENCH / "configs" / "mnist-convtm.json").read_text())


def test_conv_work_counts_at_the_mnist_geometry():
    """361 patches of 136 features, 2000 clauses, 10 classes; the engine
    block is the layout the program sizes for this model."""
    from repro import api
    cfg = _mnist()
    kind = kinds.of(cfg)
    assert kind.__name__ == "kinds.conv"
    C, L2, H, P = kind.sizes(cfg)
    assert (C, L2, H, P) == (2000, 272, 10, 361)
    assert kind.infer_ops_per_row(cfg) == 2 * C * L2 * P + 2 * C * H \
        == 392_808_000
    assert kind.train_ops_per_row(cfg) == 392_808_000 + C * L2
    ce = kind.clause_eval(cfg, rows=32, requests=1)
    assert ce["ops"] == 2 * C * L2 * P * 32
    assert ce["bytes"] == C * L2 / 8 + 32 * P * L2 / 8 + 32 * P * C / 8
    ta = kind.ta_update(cfg, rows=64, steps=2, active_share=0.5)
    assert ta["ops"] == C * L2 * 64 * 0.5
    assert ta["bytes"] == 2 * C * L2 * 2 * 0.5 + 64 * P * L2 / 8 + 4 * 64 * C
    for name in ("spec", "unpad", "motifs", "rows", "program", "hyper",
                 "predict", "train", "infer_ops_per_row",
                 "train_ops_per_row", "clause_eval", "ta_update"):
        assert callable(getattr(kind, name)), name
    tile = api.tile_for(kind.spec(cfg))
    e = cfg["engine"]
    assert tile.padded_dims() == (e["literal_columns"], e["selection_rows"],
                                  e["classes_padded"])
    assert tile.max_patches == e["patch_slots"] == P
    assert e["negated_literal_column"] == e["literal_columns"] // 2
    assert e["ta_row_stride"] == -(-e["literal_columns"] // 256) * 256


def test_the_conv_cell_reports_its_metrics():
    d = harness.load_cell(CELL)
    assert d["config"]["kind"] == "conv" and d["config"]["reduced"] == []
    assert [m["name"] for m in d["end_to_end"]] == ["train_rows_per_s",
                                                    "setup_s"]
    assert tuple(m["name"] for m in d["per_layer"]) == METRICS
    assert all(m["moves"] == "train_rows_per_s" for m in d["per_layer"])
    # the conv kernel joins the TA-update stage; the flat cells' stages
    # keep their names
    assert "ta_update_conv" in trace_reduce.stage_patterns()["ta_update"]
    assert set(trace_reduce.stage_patterns()) == {"clause_eval",
                                                  "ta_update"}


def test_conv_readers_read_the_conv_work():
    cfg = _mnist()
    kind = kinds.of(cfg)
    peak = work.peaks("TPU v5 lite")
    ctx = {"config": cfg, "peaks": peak, "train_rows": 6_400,
           "train_span_s": 4.0, "train_steps": 200, "active_share": 0.25,
           "trace": {"stage_s": {"clause_eval": 0.5, "ta_update": 2.0},
                     "devices": 1, "busy_s": 3.5, "window_s": 4.0}}
    assert harness.reader("ta_update_roofline")(ctx) == pytest.approx(
        100 * work.roofline_s(kind.ta_update(cfg, 6_400, 200, 0.25),
                              peak) / 2.0)
    assert harness.reader("clause_eval_roofline.conv_train_rows")(ctx) == (
        pytest.approx(100 * work.roofline_s(
            kind.clause_eval(cfg, 6_400, 200), peak) / 0.5))
    assert harness.reader("train_mfu")(ctx) == (
        pytest.approx(100 * kind.train_ops_per_row(cfg) * 1_600 / 393e12))
    assert harness.reader("device_idle.train_rows")(ctx) == (
        pytest.approx(12.5))


def test_conv_readers_return_nothing_without_a_trace():
    """No trace and no peaks (a run off the chip), or a trace in which no
    TA-update kernel ran: nothing to read, the metric is left out."""
    ctx = {"config": _mnist(), "train_rows": 640, "train_span_s": 1.0,
           "train_steps": 20, "active_share": 0.5, "peaks": None}
    for name in METRICS:
        assert harness.reader(name)(ctx) is None, name
    ctx.update(peaks=work.peaks("TPU v5 lite"), trace={
        "stage_s": {"clause_eval": 0.1, "ta_update": 0}, "devices": 1,
        "busy_s": 0.9, "window_s": 1.0})
    assert harness.reader("ta_update_roofline")(ctx) is None


def test_sound_conv_run_is_correct(conv_root):
    res = run_cell(conv_root)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {"wrong_ta_states", "wrong_weights",
                                    "wrong_epoch_stats"}
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert set(res["metrics"]) == {"train_rows_per_s", "setup_s"}


def test_conv_control_is_not_correct(conv_root):
    d = harness.load_cell(CELL, conv_root)
    reading = harness.control_readings(d["config"], d["traffic"], seed=7,
                                       seconds=1.0)
    assert all(v == 0 for v in reading["sound"].values()), reading
    assert all(v > harness.LIMITS[k] for k, v in reading["control"].items()
               if k != "wrong_epoch_stats"), reading


def _unchanged_state(monkeypatch):
    from repro.core.dtm import DTMEngine
    impl = DTMEngine._train_conv_impl

    def unchanged(self, prog, prng, plits, labels):
        _, _, stats = impl(self, prog, prng, plits, labels)
        return prog, prng, stats

    monkeypatch.setattr(DTMEngine, "_train_conv_impl", unchanged)


def _half_batch(monkeypatch):
    from repro.core.dtm import DTMEngine
    impl = DTMEngine._train_conv_impl

    def half(self, prog, prng, plits, labels):
        h = plits.shape[0] // 2
        return impl(self, prog, prng, plits[:h], labels[:h])

    monkeypatch.setattr(DTMEngine, "_train_conv_impl", half)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_a_broken_conv_step_is_not_correct(conv_root, monkeypatch, plant):
    jax.clear_caches()
    plant(monkeypatch)
    res = run_cell(conv_root, seed=11)
    jax.clear_caches()
    assert res["correct"] is False, res["compared"]


def test_conv_reference_follows_the_program_step_by_step():
    from repro import api
    from repro.core.prng import PRNG
    cfg = tiny_conv_config()
    kind = kinds.of(cfg)
    spec = kind.spec(cfg)
    eng = api.compile(api.tile_for(spec), backend="ref")
    mot = kind.motifs(cfg)
    ta, w = kind.program(cfg, mot, "paper_init", 5, 0)
    x, y = kind.rows(cfg, mot, 5, 3 * 8)
    prog = eng.lower(spec, jax.random.PRNGKey(0), ta=ta, weights=w)
    prng = PRNG.create(spec.tm_config(), 99)
    for s in range(3):
        prog, prng, _ = eng.train_conv(prog, prng,
                                       eng.encode(spec, x[8 * s:8 * s + 8]),
                                       y[8 * s:8 * s + 8])
    (r_ta, r_w, _), _ = kind.train(kind.hyper(cfg), ta, w, 99,
                                   x.reshape(3, 8, 10, 10), y.reshape(3, 8))
    p_ta, p_w = kind.unpad(cfg, prog.ta, prog.weights)
    assert np.array_equal(p_ta, np.asarray(r_ta))
    assert np.array_equal(p_w, np.asarray(r_w))
    assert not np.array_equal(p_ta, np.asarray(ta, np.int32))
