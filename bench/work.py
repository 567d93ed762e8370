"""Peaks and rooflines, the part of the work arithmetic every kind shares.

What the algorithm needs is counted by the kind module of the cell's
configuration (``kinds/<kind>.py``: ``infer_ops_per_row``,
``train_ops_per_row``, ``clause_eval``, ``ta_update``) from its published
sizes, never from the engine's padded geometry: padding is not useful
work.  Operations are counted the way the int8 peak counts them (a
multiply and an add are two), so a share of that peak is a fair
utilisation.

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


def roofline_s(work: dict, peak: dict) -> float:
    return max(work["ops"] / peak["int8_ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
