"""Conv TM module (paper §VI roadmap; compared against the Conv TM
accelerator [40] in Table I): position-invariance demonstration — ConvTM vs
flat CoTM on motifs placed at random image positions."""
from __future__ import annotations

import numpy as np

from repro.api import TM, TMSpec

from .common import FAST, row, time_call


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    motifs = np.array([
        [[1, 1, 1], [0, 0, 0], [1, 1, 1]],
        [[1, 0, 1], [1, 0, 1], [1, 0, 1]],
        [[0, 1, 0], [1, 1, 1], [0, 1, 0]],
    ], np.int8)
    y = rng.integers(0, 3, n).astype(np.int32)
    x = (rng.random((n, 8, 8)) < 0.05).astype(np.int8)
    for i in range(n):
        r, c = rng.integers(0, 6, 2)
        x[i, r:r + 3, c:c + 3] = motifs[y[i]]
    return x, y


def run() -> None:
    n = 640 if FAST else 1024
    x, y = _data(n)
    ntr = n - 128
    xtr, ytr, xte, yte = x[:ntr], y[:ntr], x[ntr:], y[ntr:]

    epochs = 4 if FAST else 6
    ctm = TM(TMSpec.conv(img_h=8, img_w=8, patch=3, classes=3, clauses=48,
                         T=12, s=3.0), seed=0)
    ctm.fit(xtr, ytr, epochs=epochs, batch=32)
    acc_conv = ctm.score(xte, yte)
    us = time_call(lambda: ctm.partial_fit(xtr[:32], ytr[:32]))
    row("convtm/translated_motifs", us / 32, f"acc={acc_conv:.3f}")

    fspec = TMSpec.coalesced(features=64, classes=3, clauses=48, T=12,
                             s=3.0, prng_backend="threefry")
    ftm = TM(fspec, seed=0)
    ftm.fit(xtr.reshape(ntr, 64), ytr, epochs=epochs, batch=32)
    acc_flat = ftm.score(xte.reshape(-1, 64), yte)
    row("convtm/flat_cotm_baseline", 0.0,
        f"acc={acc_flat:.3f};invariance_gap={acc_conv - acc_flat:+.3f}")


if __name__ == "__main__":
    run()
