"""Work the Coalesced TM algorithm needs, counted from the configuration's
published sizes (C clauses, 2F literals, H classes), never from the
engine's padded geometry: padding is not useful work.

Operations are counted the way the int8 peak counts them (a multiply and
an add are two), so a share of that peak is a fair utilisation:

* inference, per row: clause evaluation 2·C·2F (one include-and-literal
  test and one accumulate per TA) plus class sums 2·C·H;
* training, per row: the inference operations plus one update of every
  (clause, literal) TA cell, C·2F (the two feedback rounds of a row are
  summed per cell before the clip, so a cell is written once per row).
  Recomputed work does not count.

Bytes are the least a stage must move through HBM:

* clause evaluation: the served model's include plane once per request
  (C·2F bits), the row's literals (2F bits) and one clause bit per
  clause and row (C/8 bytes);
* TA update: every TA state of the clause groups that received feedback,
  read and written once per step (``ceil(ta_bits/8)`` bytes a state);
  its operations too count only those groups' cells.

A roofline time is the larger of operations over the int8 peak and bytes
over the HBM peak (``peaks.json``); a stage's roofline share is that time
over the device time of the stage's kernels.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def sizes(cfg: dict) -> tuple:
    return cfg["clauses"], 2 * cfg["features"], cfg["classes"]


def infer_ops_per_row(cfg: dict) -> int:
    C, L2, H = sizes(cfg)
    return 2 * C * L2 + 2 * C * H


def train_ops_per_row(cfg: dict) -> int:
    C, L2, _ = sizes(cfg)
    return infer_ops_per_row(cfg) + C * L2


def clause_eval(cfg: dict, rows: int, requests: int) -> dict:
    C, L2, _ = sizes(cfg)
    return {"ops": 2 * C * L2 * rows,
            "bytes": requests * C * L2 / 8 + rows * L2 / 8 + rows * C / 8}


def ta_update(cfg: dict, rows: int, steps: int, active_share: float) -> dict:
    C, L2, _ = sizes(cfg)
    state = -(-cfg["ta_bits"] // 8)
    return {"ops": C * L2 * rows * active_share,
            "bytes": 2 * state * C * L2 * steps * active_share}


def roofline_s(work: dict, peak: dict) -> float:
    return max(work["ops"] / peak["int8_ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
