"""Paper §VI future-work modules: the Convolutional TM's plain reference
(``bench/kinds/conv_ref.py``) and the Regression TM."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Every test here trains a TM variant for multiple epochs (2-5 s each) —
# nightly tier; tier-1 runs -m "not slow".
pytestmark = pytest.mark.slow

from repro.core import to_literals
from repro.core.regression_tm import (RegressionTMConfig, init as rtm_init,
                                      predict as rtm_predict,
                                      train_step as rtm_step)

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from kinds import conv  # noqa: E402


def _conv_config(img: int, patch: int, clauses: int, classes: int,
                 T: int) -> dict:
    """``mnist-convtm`` at a toy size; the engine block only keys the
    reference's random streams, so any layout that holds the model does."""
    cfg = json.loads((BENCH / "configs" / "mnist-convtm.json").read_text())
    cfg.update(img_h=img, img_w=img, patch=patch, clauses=clauses,
               classes=classes, T=T, s=3.0)
    cfg["engine"] = dict(literal_columns=128, selection_rows=128,
                         classes_padded=8, negated_literal_column=64,
                         ta_row_stride=256, skip_group_rows=128,
                         draw_lanes=8192,
                         patch_slots=(img - patch + 1) ** 2)
    return cfg


def _translated_motifs(n, seed=0):
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


def test_conv_tm_position_invariance():
    """The conv reference classifies motifs at RANDOM positions (flat TMs
    cannot — measured gap > 0.4; see benchmarks/convtm_bench.py)."""
    cfg = _conv_config(img=8, patch=3, clauses=48, classes=3, T=12)
    hp = conv.hyper(cfg)
    ta, w = conv.program(cfg, None, "paper_init", 0, 0)
    x, y = _translated_motifs(640)
    xs = jnp.asarray(x[:512].reshape(16, 32, 8, 8))
    ys = jnp.asarray(y[:512].reshape(16, 32))
    for ep in range(4):
        (ta, w, _), _ = conv.train(hp, ta, w, 1 + ep, xs, ys)
    pred = np.asarray(conv.predict(hp, ta, w, jnp.asarray(x[512:])))
    assert (pred == y[512:]).mean() > 0.85


def test_conv_tm_state_bounds():
    cfg = _conv_config(img=6, patch=3, clauses=16, classes=2, T=8)
    ta, w = conv.program(cfg, None, "paper_init", 0, 0)
    x, y = _translated_motifs(32)
    (ta, _, _), _ = conv.train(conv.hyper(cfg), ta, w, 1,
                               jnp.asarray(x[None, :, :6, :6]),
                               jnp.asarray(y[None] % 2))
    ta = np.asarray(ta)
    assert ta.min() >= 0 and ta.max() <= (1 << cfg["ta_bits"]) - 1


def test_regression_tm_learns_boolean_function():
    rng = np.random.default_rng(0)
    f = 12
    x = (rng.random((1024, f)) < 0.5).astype(np.int8)
    y = (0.6 * x[:, 0] + 0.3 * (x[:, 1] & x[:, 2])
         + 0.1 * x[:, 3]).astype(np.float32)
    xtr, ytr, xte, yte = x[:768], y[:768], x[768:], y[768:]
    cfg = RegressionTMConfig(features=f, clauses=128, T=128, s=3.0)
    state, prng = rtm_init(cfg, jax.random.PRNGKey(0))
    step = jax.jit(lambda s, p, l, t: rtm_step(cfg, s, p, l, t))
    for ep in range(10):
        for i in range(0, 768, 32):
            state, prng, _ = step(state, prng,
                                  to_literals(jnp.asarray(xtr[i:i + 32])),
                                  jnp.asarray(ytr[i:i + 32]))
    pred = np.asarray(rtm_predict(cfg, state,
                                  to_literals(jnp.asarray(xte))))
    mae = np.abs(pred - yte).mean()
    base = np.abs(yte.mean() - yte).mean()
    assert mae < base * 0.8, (mae, base)


def test_regression_tm_prediction_range():
    cfg = RegressionTMConfig(features=8, clauses=32, T=32)
    state, prng = rtm_init(cfg, jax.random.PRNGKey(0))
    lits = to_literals(jnp.ones((4, 8), jnp.int8))
    p = np.asarray(rtm_predict(cfg, state, lits))
    assert (p >= 0).all() and (p <= 1).all()
