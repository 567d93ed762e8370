"""The paper's headline demo, full width: ONE compiled DTM engine, FIVE
TM variants — Coalesced, Vanilla, Convolutional, Regression, and a
booleanized feature head — each lowered to a DTMProgram and trained /
evaluated on the same jitted stage executables.  At the end we prove no
recompilation happened (every engine stage holds exactly one jit cache
entry), i.e. run-time reconfiguration without "resynthesis" (paper §IV-A,
Table II) across the whole model family.

PYTHONPATH=src python examples/dtm_reconfigure.py
"""
import time

import numpy as np

from repro import api
from repro.api import TM, TMSpec
from repro.data import KWS6_LIKE, MNIST_LIKE, make_bool_dataset
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

rng = np.random.default_rng(0)
B = 32


def flat_task(spec_like, n=768):
    x, y = make_bool_dataset(spec_like, n)
    return x[:512], y[:512], x[512:], y[512:]


def conv_task(n=640):
    """Translated 3x3 motifs — flat TMs cannot solve this one."""
    motifs = np.array([[[1, 1, 1], [0, 0, 0], [1, 1, 1]],
                       [[1, 0, 1], [1, 0, 1], [1, 0, 1]],
                       [[0, 1, 0], [1, 1, 1], [0, 1, 0]]], np.int8)
    y = rng.integers(0, 3, n).astype(np.int32)
    x = (rng.random((n, 8, 8)) < 0.05).astype(np.int8)
    for i in range(n):
        r, c = rng.integers(0, 6, 2)
        x[i, r:r + 3, c:c + 3] = motifs[y[i]]
    return x[:512], y[:512], x[512:], y[512:]


def regression_task(n=768):
    x = (rng.random((n, 12)) < 0.5).astype(np.int8)
    y = (0.6 * x[:, 0] + 0.3 * (x[:, 1] & x[:, 2])
         + 0.1 * x[:, 3]).astype(np.float32)
    return x[:512], y[:512], x[512:], y[512:]


def head_task(n=640):
    protos = rng.standard_normal((3, 16))
    y = rng.integers(0, 3, n).astype(np.int32)
    feats = (protos[y] + 0.3 * rng.standard_normal((n, 16))
             ).astype(np.float32)
    return feats[:512], y[:512], feats[512:], y[512:]


xh, yh, xh_te, yh_te = head_task()
MODELS = {
    "mnist-like/CoTM": (TMSpec.coalesced(
        features=MNIST_LIKE.features, classes=10, clauses=256, T=48, s=6.0),
        flat_task(MNIST_LIKE), 4),
    "kws6-like/Vanilla": (TMSpec.vanilla(
        features=KWS6_LIKE.features, classes=6, clauses=32, T=16, s=4.0),
        flat_task(KWS6_LIKE), 4),
    "motifs/Conv": (TMSpec.conv(
        img_h=8, img_w=8, patch=3, classes=3, clauses=48, T=12, s=3.0),
        conv_task(), 4),
    "votes/Regression": (TMSpec.regression(
        features=12, clauses=128, T=128, s=3.0), regression_task(), 6),
    "features/Head": (TMSpec.head(
        xh[:128], classes=3, therm_bits=4, clauses=32, T=16, s=4.0),
        (xh, yh, xh_te, yh_te), 3),
}

# the 'synthesised' accelerator: ONE engine sized for the whole roster
tile = api.tile_for(*(spec for spec, _, _ in MODELS.values()))
engine = api.compile(tile)
print(f"engine buffers: literals={engine.L} clauses={engine.R} "
      f"classes={engine.H} patches={engine.P}  backend={engine.backend}")

for name, (spec, (xtr, ytr, xte, yte), epochs) in MODELS.items():
    tm = TM(spec, engine=engine, seed=0)      # lower = data, not code
    t0 = time.time()
    tm.fit(xtr, ytr, epochs=epochs, batch=B)
    score = tm.score(xte, yte, batch=64)
    metric = "acc" if spec.kind != "regression" else "-mae"
    print(f"{name:20s} {metric}={score:+.3f}  ({time.time() - t0:.1f}s)")

report = engine.cache_report()
print(f"compiled stage executables: {report}")
print("(every stage == 1 entry: five TM variants, ZERO recompilations — "
      "the session epoch executables stay at one entry too because the "
      "roster standardises dataset slots, 512 samples x batch 32, the "
      "same fixed-slot discipline serve_tm uses for requests)")
assert all(v <= 1 for v in report.values() if isinstance(v, int)), report
# TM.fit is session-backed: training runs through the one-scan-per-epoch
# executables, inference through the per-batch infer stage
assert report["infer"] == 1 and report["fit_epoch"] == 1
assert report["fit_epoch_conv"] == 1
print(f"kernel path per stage: {report['path_per_stage']}")
