"""Harness tests run on the CPU, at tiny sizes, in one process each
(``python -m pytest bench/tests``).  ``tiny_root`` is a checkout-shaped
directory whose ``BENCHMARK.json`` keeps the real metrics and one cell of
each traffic kind the generator drives, named as the real cells, at a
tiny configuration."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAFFIC = {
    "cotm-edge-b1-zipf": {
        "tenants": 12, "batch_slot": 1, "programs": "trained_like",
        "pool_rows": 512, "scheduler": {"resident_slots": 4},
        "open": {"rate_rps": 40, "rows": [[1, 1]],
                 "zipf": 1.1},
        "check": {"sample_requests": 50, "control": {"weight_bits": 8}}},
    "kws6-batch-b32": {
        "tenants": 4, "batch_slot": 8, "programs": "trained_like",
        "pool_rows": 512, "closed": {"rows": 8},
        "check": {"sample_requests": 50, "control": {"weight_bits": 8}}},
    "cotm-fit-b32": {"programs": "paper_init",
                     "fit": {"rows": 256, "batch": 8},
                     "check": {"control": {"rand_bits": 8}}},
}


def tiny_config() -> dict:
    cfg = json.loads((BENCH / "configs" / "mnist-cotm.json").read_text())
    cfg.update(name="tiny", features=20, clauses=30, classes=4, T=15, s=3.9)
    # the layout the program's tile_for gives 40 literals / 30 clauses
    cfg["engine"] = dict(literal_columns=128, selection_rows=128,
                         classes_padded=8, negated_literal_column=64,
                         ta_row_stride=256, skip_group_rows=128,
                         draw_lanes=8192)
    cfg["dataset"].update(motifs_per_class=3, motif_bits=4, active_motifs=2,
                          neg_includes=3)
    return cfg


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config()))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny"}]
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": n,
                           "chips": 1, "why": "tiny"} for n in TINY_TRAFFIC]
    for n, traffic in TINY_TRAFFIC.items():
        (root / "bench" / "traffic" / f"{n}.json").write_text(
            json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
