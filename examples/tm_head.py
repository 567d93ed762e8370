"""TM readout head over frozen backbone features (DESIGN.md §5) — the
paper's "multivariate sensor task" deployment: pooled float features from
any frozen feature extractor are thermometer-Booleanised and a CoTM
learns the classification with integer-only training.

Unified API: the head is ``TMSpec.head(calib, ...)`` — the booleanizer is
folded into the spec, and the program runs on the same compiled-once DTM
engine as every other TM variant.  The backbone here is a stand-in:
fixed random projections of a synthetic 3-class signal, i.e. the same
pooled-embedding shape an upstream encoder would hand over.

PYTHONPATH=src python examples/tm_head.py
"""
import numpy as np

from repro.api import TM, TMSpec
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

# synthetic 3-way "sensor" task behind a frozen random-projection
# backbone: class-dependent means, fixed mixing matrix, pooled features
rng = np.random.default_rng(0)
N, D_RAW, D_FEAT = 600, 24, 8
y = rng.integers(0, 3, N).astype(np.int32)
means = rng.standard_normal((3, D_RAW)).astype(np.float32) * 1.5
raw = means[y] + rng.standard_normal((N, D_RAW)).astype(np.float32)
backbone = rng.standard_normal((D_RAW, D_FEAT)).astype(np.float32)
feats = np.tanh(raw @ backbone)                     # pooled "embeddings"

spec = TMSpec.head(feats[:128], classes=3, therm_bits=6, clauses=128,
                   T=32, s=4.0)
head = TM(spec, seed=0)
head.fit(feats[:448], y[:448], epochs=5, batch=32)
acc = head.score(feats[448:], y[448:], batch=64)
print(f"TM-head accuracy on backbone features: {acc:.3f}")
assert acc > 0.7
