"""Unified compile/program/run front-end (ISSUE 2 acceptance).

The flexibility contract, widened from "2 models, 1 cache entry" to the
whole family: all FIVE TM variants (CoTM, Vanilla, Conv, Regression,
Head) lower to :class:`DTMProgram` data and execute on ONE compiled
:class:`DTMEngine` — every engine stage executable holds exactly one jit
cache entry across arbitrary program swaps, results are bit-identical
between the ``kernel`` and ``ref`` backends, and re-running a program
after the full swap cycle reproduces its outputs exactly (programs are
pure data; the engine holds no model state).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import TM, TMSpec
from repro.core import PRNG, DTMProgram

BATCH = 8
_rng = np.random.default_rng(42)
_CALIB = _rng.standard_normal((64, 8)).astype(np.float32)

SPECS = {
    "cotm": TMSpec.coalesced(features=20, classes=3, clauses=24, T=8, s=3.0),
    "vanilla": TMSpec.vanilla(features=16, classes=4, clauses=8, T=8, s=3.0),
    "conv": TMSpec.conv(img_h=6, img_w=6, patch=3, classes=2, clauses=16,
                        T=8, s=3.0),
    "regression": TMSpec.regression(features=12, clauses=16, T=16, s=3.0),
    "head": TMSpec.head(_CALIB, classes=3, therm_bits=2, clauses=16, T=8,
                        s=3.0),
}


def _batch(spec: TMSpec, seed: int = 5):
    rng = np.random.default_rng(seed)
    cfg = spec.tm_config()
    if spec.kind == "conv":
        x = (rng.random((BATCH, 6, 6)) < 0.3).astype(np.int8)
        y = rng.integers(0, 2, BATCH).astype(np.int32)
    elif spec.kind == "head":
        x = rng.standard_normal((BATCH, 8)).astype(np.float32)
        y = rng.integers(0, 3, BATCH).astype(np.int32)
    elif spec.kind == "regression":
        x = (rng.random((BATCH, 12)) < 0.5).astype(np.int8)
        y = np.round(rng.random(BATCH) * cfg.T).astype(np.int32)
    else:
        x = (rng.random((BATCH, cfg.features)) < 0.5).astype(np.int8)
        y = rng.integers(0, cfg.classes, BATCH).astype(np.int32)
    return x, y


def _run_variant(eng, spec, x, y):
    prog = eng.lower(spec, jax.random.PRNGKey(0))
    prng = PRNG.create(spec.tm_config(), 7)
    lits = eng.encode(spec, jnp.asarray(x))
    step = eng.train_conv if spec.kind == "conv" else eng.train_step
    infer = eng.infer_conv if spec.kind == "conv" else eng.infer
    new_prog, _, stats = step(prog, prng, lits, jnp.asarray(y))
    sums, cl = infer(prog, lits)
    return {"ta": np.asarray(new_prog.ta),
            "weights": np.asarray(new_prog.weights),
            "sums": np.asarray(sums), "cl": np.asarray(cl),
            "stats": {k: int(v) for k, v in stats.items()}}


@functools.lru_cache(maxsize=None)
def _roster_results(backend: str):
    """Cycle all five variants on one engine; return per-variant outputs
    plus the engine's cache report and a re-run of the first variant."""
    tile = api.tile_for(*SPECS.values(), x=32, y=16, m=16, n=4)
    eng = api.compile(tile, backend=backend)
    out = {}
    for name, spec in SPECS.items():
        out[name] = _run_variant(eng, spec, *_batch(spec))
    rerun = _run_variant(eng, SPECS["cotm"], *_batch(SPECS["cotm"]))
    return out, rerun, eng.cache_report()


@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_program_swap_keeps_cache_at_one(backend):
    """Five variants + a swap back, zero recompilations of any stage."""
    out, rerun, report = _roster_results(backend)
    report = dict(report)            # don't mutate the lru_cached dict
    paths = report.pop("path_per_stage")
    # the four per-program stages compiled exactly once; the session /
    # bank executables this roster never exercises stay at zero
    assert {k: v for k, v in report.items() if v} == {
        "infer": 1, "train": 1, "infer_conv": 1, "train_conv": 1}, report
    assert all(v <= 1 for v in report.values()), report
    # dispatch == execution: every traced stage recorded the path the
    # dispatcher selects for its batch size (BATCH=8 -> throughput paths
    # by default; an env force like REPRO_KERNEL_PATH=packed_vpu must be
    # honoured by every stage — the old silent mxu fallback is the bug)
    from repro.kernels import select_path, select_ta_path

    # the engine dispatches on its padded (L, R, H) shape so the autotune
    # plan cache can key on geometry; mirror that here
    shape = api.tile_for(*SPECS.values(), x=32, y=16, m=16, n=4).padded_dims()

    def expect(batch, training=False):
        path = select_path(None, batch=batch, training=training, shape=shape)
        if not training and path == "fused":     # eval has no fused impl
            path = "mxu"
        if backend == "ref" and path not in ("packed_vpu", "mxu_popcount"):
            path = "ref"                         # jnp oracles ARE the path
        return path

    # conv stages run clause eval on the flattened [B·P] patch batch
    conv_batch = BATCH * max(s.n_patches for s in SPECS.values())
    # the train stage also records the SKIP dimension of its TA-update
    # back half (compact by default; dense under REPRO_SKIP=0) and the
    # PRNG stream family/placement of the Alg-5 update; the conv train
    # stage records its TA-update backend (the ta_update_conv kernel or
    # its oracle) and the same stream family, generated in place
    assert paths == {"infer": expect(BATCH),
                     "train": expect(BATCH, training=True),
                     "train_ta": select_ta_path(shape=shape),
                     "train_prng": "counter-inkernel",
                     "infer_conv": expect(conv_batch),
                     "train_conv": expect(conv_batch),
                     "train_conv_ta": ("ref" if backend == "ref"
                                       else "pallas"),
                     "train_conv_ta_prng": "counter-inkernel"}, paths
    # programs are pure data: swapping through the whole roster and back
    # reproduces the first variant's outputs bit-for-bit
    first = out["cotm"]
    for k in ("ta", "weights", "sums", "cl"):
        np.testing.assert_array_equal(first[k], rerun[k], err_msg=k)
    assert first["stats"] == rerun["stats"]


def test_program_swap_backend_parity():
    """kernel (Pallas) and ref (jnp) backends are bit-identical for every
    variant — TA states, weights, class sums, clause outputs, stats."""
    ref, _, _ = _roster_results("ref")
    ker, _, _ = _roster_results("kernel")
    for name in SPECS:
        for k in ("ta", "weights", "sums", "cl"):
            np.testing.assert_array_equal(ref[name][k], ker[name][k],
                                          err_msg=f"{name}/{k}")
        assert ref[name]["stats"] == ker[name]["stats"], name


def test_program_flatten_identity():
    """tree_flatten must hand out the field references themselves (no
    astuple deep-copy — flatten runs on every jit dispatch)."""
    eng = api.compile(api.tile_for(SPECS["cotm"], x=32, y=16, m=16, n=4),
                      backend="ref")
    prog = eng.lower(SPECS["cotm"], jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(prog)
    n_fields = len(dataclasses.fields(DTMProgram))
    assert len(leaves) == n_fields
    assert leaves[0] is prog.ta and leaves[1] is prog.weights
    rt = jax.tree_util.tree_unflatten(treedef, leaves)
    for f in dataclasses.fields(DTMProgram):
        assert getattr(rt, f.name) is getattr(prog, f.name), f.name


def test_lower_rejects_oversized_spec():
    tile = api.tile_for(SPECS["vanilla"], x=32, y=16, m=16, n=4)
    eng = api.compile(tile, backend="ref")
    too_big = TMSpec.conv(img_h=8, img_w=8, patch=3, classes=2, clauses=8)
    with pytest.raises(AssertionError, match="patch slots"):
        eng.lower(too_big, jax.random.PRNGKey(0))


def test_lower_rejects_rand_bits_mismatch(tmp_path):
    """Spec PRNG width and engine fixed-point shift must agree, or every
    Alg-3 select probability silently collapses."""
    spec = TMSpec.coalesced(features=8, classes=2, clauses=8, T=8, s=3.0,
                            rand_bits=8)
    eng = api.compile(api.tile_for(spec, x=32, y=16, m=16, n=4),
                      backend="ref")              # engine default: 16
    with pytest.raises(AssertionError, match="rand_bits"):
        eng.lower(spec, jax.random.PRNGKey(0))
    # the estimator shell plumbs the spec's width into compile()...
    tm = TM(spec, tile=api.tile_for(spec, x=32, y=16, m=16, n=4),
            backend="ref")
    assert tm.engine.rand_bits == 8
    x, y = _batch(TMSpec.coalesced(features=8, classes=2, clauses=8,
                                   T=8, s=3.0))
    tm.partial_fit(x, y)
    # ...and so does TM.load when it rebuilds the engine from a checkpoint
    tm.save(str(tmp_path))
    tm2 = TM.load(str(tmp_path))
    assert tm2.engine.rand_bits == 8


def test_estimator_history_and_save_load(tmp_path):
    spec = SPECS["cotm"]
    x, y = _batch(spec)
    tm = TM(spec, tile=api.tile_for(spec, x=32, y=16, m=16, n=4),
            backend="ref", seed=0)
    hist = tm.fit(x, y, epochs=2, batch=4)
    assert {"epoch", "train_acc", "selected_clauses",
            "group_skip_frac"} <= set(hist[0])
    tm.save(str(tmp_path))
    tm2 = TM.load(str(tmp_path))
    assert tm2.spec.kind == spec.kind and tm2.steps == tm.steps
    np.testing.assert_array_equal(np.asarray(tm.program.ta),
                                  np.asarray(tm2.program.ta))
    p1 = np.asarray(tm.predict(jnp.asarray(x)))
    p2 = np.asarray(tm2.predict(jnp.asarray(x)))
    np.testing.assert_array_equal(p1, p2)


def test_regression_estimator_predicts_in_unit_range(tmp_path):
    spec = SPECS["regression"]
    x, _ = _batch(spec)
    tm = TM(spec, tile=api.tile_for(spec, x=32, y=16, m=16, n=4),
            backend="ref", seed=0)
    p = np.asarray(tm.predict(jnp.asarray(x)))
    assert p.dtype == np.float32 and (p >= 0).all() and (p <= 1).all()


@pytest.mark.slow
def test_unified_conv_and_regression_learn():
    """Quality parity of the lowered variants: the engine's conv and
    regression programs actually learn their bespoke-module tasks."""
    rng = np.random.default_rng(0)
    motifs = np.array([[[1, 1, 1], [0, 0, 0], [1, 1, 1]],
                       [[1, 0, 1], [1, 0, 1], [1, 0, 1]],
                       [[0, 1, 0], [1, 1, 1], [0, 1, 0]]], np.int8)
    y = rng.integers(0, 3, 640).astype(np.int32)
    x = (rng.random((640, 8, 8)) < 0.05).astype(np.int8)
    for i in range(640):
        r, c = rng.integers(0, 6, 2)
        x[i, r:r + 3, c:c + 3] = motifs[y[i]]
    conv = TM(TMSpec.conv(img_h=8, img_w=8, patch=3, classes=3, clauses=48,
                          T=12, s=3.0), seed=0)
    conv.fit(x[:512], y[:512], epochs=4, batch=32)
    assert conv.score(x[512:], y[512:], batch=64) > 0.85

    f = 12
    xr = (rng.random((1024, f)) < 0.5).astype(np.int8)
    yr = (0.6 * xr[:, 0] + 0.3 * (xr[:, 1] & xr[:, 2])
          + 0.1 * xr[:, 3]).astype(np.float32)
    reg = TM(TMSpec.regression(features=f, clauses=128, T=128, s=3.0),
             seed=0)
    reg.fit(xr[:768], yr[:768], epochs=10, batch=32)
    mae = -reg.score(xr[768:], yr[768:])
    base = np.abs(yr[768:].mean() - yr[768:]).mean()
    assert mae < base * 0.8, (mae, base)
