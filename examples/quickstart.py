"""Quickstart: the unified compile/program/run API in ~15 lines.

A TMSpec describes the model; the TM estimator lowers it onto a
compiled-once DTM engine and drives training/eval (fit/predict/score).
Swap `TMSpec.coalesced` for `.vanilla(...)`, `.conv(...)`,
`.regression(...)` or `.head(...)` — same shell, same engine design.

PYTHONPATH=src python examples/quickstart.py
"""
from repro.api import TM, TMSpec
from repro.data import MNIST_LIKE, make_bool_dataset
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

# 784 Boolean features, 10 classes — MNIST geometry (synthetic surrogate).
x, y = make_bool_dataset(MNIST_LIKE, 1024)
xtr, ytr, xte, yte = x[:768], y[:768], x[768:], y[768:]

spec = TMSpec.coalesced(
    features=MNIST_LIKE.features,
    classes=MNIST_LIKE.classes,
    clauses=256,           # shared clause pool (Fig 1e)
    T=48, s=6.0,           # threshold + sensitivity hyper-parameters
)
tm = TM(spec, seed=0)
history = tm.fit(xtr, ytr, epochs=5, batch=32, x_test=xte, y_test=yte)
for h in history:
    print(h)
acc = tm.score(xte, yte)
print(f"final test accuracy: {acc:.3f}")
assert acc > 0.8, acc
