"""Kernel-speed pass (ISSUE 8 acceptance): in-kernel LFSR PRNG,
popcount-as-matmul packed eval, and the measured path autotuner.

The three optimisations must be pure wall-clock changes — never semantic:

* the TA-update random stream generated INSIDE the Pallas kernels (each
  tile advancing its own LFSR/counter lanes keyed on the element's global
  index) is bit-identical to the streamed baseline that materialises the
  same [B, C, L] tensor in HBM, on both backends, for both stream
  families, with and without the paper's master-slave seed refresh;
* the LFSR lane construction matches ``core.prng`` exactly (same taps,
  same splitmix seeding, same refresh schedule) so Fig-15 quality sweeps
  transfer to the kernel path unchanged;
* ``packed_clause_eval_mxu`` (popcount as an int8 matmul) == the VPU word
  path == the jnp oracles, fired/empty semantics included, on ragged
  literal counts;
* autotune plans only ever re-route between bit-identical paths: engine
  training is invariant across {REPRO_AUTOTUNE off/seed} ×
  {REPRO_TA_PRNG inkernel/stream} × {forced packed_vpu/mxu_popcount} ×
  backends for all five TMSpec kinds;
* config-level validation: a typo'd ``prng_backend`` raises at TMSpec /
  TMConfig construction (and in distributed lowering) instead of silently
  training with threefry.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import TMSpec
from repro.core import prng as core_prng
from repro.core.types import TMConfig
from repro.kernels import (ops as kops, ref, autotune,
                           packed_clause_eval_op, packed_clause_mxu_op,
                           resolve_ta_prng, select_path, ta_update_op)

_rng = np.random.default_rng(11)
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

# (prng, lfsr_bits, seed_refresh) — lfsr_bits=4 with B past the 15-cycle
# period exercises the in-kernel master-slave re-seed branch; lfsr_bits=12
# is narrower than the 16-bit comparison, so its words are zero-extended
# (no raw-lane threshold)
STREAMS = [("counter", 24, True), ("lfsr", 24, True), ("lfsr", 4, True),
           ("lfsr", 8, False), ("lfsr", 12, True)]

# (prng, lfsr_bits, B, boost): the static choices of the TA-update tile
# body — the re-seed compiled in only from B = 2^4 - 1 = 15 (B = 14 is one
# element short of it), the zero-extended word of a 12-bit LFSR, boost
# off (a low draw cancels a Type I +1), and B = 130 past one 128-element
# counting run (events expanded per run, not once per clause tile)
BODY_CASES = [("lfsr", 4, 14, True), ("lfsr", 4, 15, True),
              ("lfsr", 12, 6, True), ("lfsr", 24, 6, False),
              ("counter", 24, 6, False), ("lfsr", 24, 130, True)]


def _ta_inputs(C, L, B, seed=0):
    rng = np.random.default_rng(seed)
    ta = jnp.asarray(rng.integers(0, 256, (C, L)), jnp.int32)
    lit = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int8)
    cl = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
    t1 = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
    t2 = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
    lm = jnp.asarray(rng.integers(0, 2, (L,)), jnp.int32)
    return ta, lit, cl, t1, t2, lm


# ---------------------------------------------------------------------------
# PRNG stream construction
# ---------------------------------------------------------------------------

def test_lfsr_taps_pinned_to_core():
    """kernels/ref.py duplicates the Galois tap table so the kernels
    package stays import-free of core — the two must never drift."""
    assert ref.LFSR_TAPS == core_prng._TAPS


def test_rand_stream_matches_core_cluster():
    """With xt | L the flattened stream keys are arange(C*L), so the
    kernel's per-element LFSR lanes ARE the core make_cluster lanes: the
    streamed tensor must equal B cluster_next cycles of a C*L-lane
    cluster, refresh schedule included (lfsr_bits=4 -> period 15 < B)."""
    C, L, B, bits, rb = 8, 32, 20, 4, 16
    got = np.asarray(ref.ta_rand_stream(7, B, C, L, rand_bits=rb,
                                        prng="lfsr", lfsr_bits=bits,
                                        seed_refresh=True, xt=L))
    st = core_prng.make_cluster(7, C * L, bits)
    for b in range(B):
        st, vals = core_prng.cluster_next(st, bits, True, rb)
        np.testing.assert_array_equal(got[b].reshape(-1), np.asarray(vals),
                                      err_msg=f"cycle {b}")


@pytest.mark.parametrize("prng,bits,refresh", STREAMS)
def test_ta_update_kernel_matches_ref(prng, bits, refresh):
    """Dense in-kernel PRNG == the jnp oracle on a ragged shape (tile
    remainders force masked lanes whose streams must not perturb live
    ones).  B=20 crosses the refresh boundary at lfsr_bits=4."""
    C, L, B = 48, 130, 20
    ta, lit, cl, t1, t2, lm = _ta_inputs(C, L, B)
    want = ref.ta_update_ref(ta, lit, cl, t1, t2, lm, 3, 9000,
                             prng=prng, lfsr_bits=bits, seed_refresh=refresh)
    got = ta_update_op(ta, lit, cl, t1, t2, lm, 3, 9000, backend="pallas",
                       prng=prng, lfsr_bits=bits, seed_refresh=refresh)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("prng,bits,refresh", STREAMS)
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_stream_equals_inkernel(backend, prng, bits, refresh):
    """REPRO_TA_PRNG=stream materialises the random tensor in HBM and
    feeds it to a consuming kernel; the numbers must be the ones the
    in-kernel generator produces in place."""
    C, L, B = 40, 100, 6
    ta, lit, cl, t1, t2, lm = _ta_inputs(C, L, B, seed=2)
    kw = dict(prng=prng, lfsr_bits=bits, seed_refresh=refresh,
              backend=backend)
    ink = ta_update_op(ta, lit, cl, t1, t2, lm, 5, 11000, **kw)
    stm = ta_update_op(ta, lit, cl, t1, t2, lm, 5, 11000, stream=True, **kw)
    np.testing.assert_array_equal(np.asarray(ink), np.asarray(stm))


@pytest.mark.parametrize("prng,bits,refresh", STREAMS)
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_compact_matches_dense_under_lfsr(backend, prng, bits, refresh):
    """The Alg-6 sparse/compact kernel advances the SAME per-element
    streams as the dense kernel (keys carry the original row index
    through the gather), for both stream families."""
    C, L, B = 64, 96, 3
    ta, lit, cl, t1, t2, lm = _ta_inputs(C, L, B, seed=4)
    rng = np.random.default_rng(9)
    act = jnp.asarray(rng.integers(0, 2, (C,)), jnp.int8)
    t1a, t2a = t1 * act[None, :], t2 * act[None, :]
    inc = ref.pack_include(ta, 256)
    kw = dict(prng=prng, lfsr_bits=bits, seed_refresh=refresh,
              backend=backend)
    d_ta, d_inc = ta_update_op(ta, lit, cl, t1a, t2a, lm, 7, 13000,
                               emit_include=True, **kw)
    c_ta, c_inc = kops.ta_update_compact_op(ta, lit, cl, t1a, t2a, lm, inc,
                                            7, 13000, **kw)
    np.testing.assert_array_equal(np.asarray(d_ta), np.asarray(c_ta))
    np.testing.assert_array_equal(np.asarray(d_inc), np.asarray(c_inc))


@pytest.mark.parametrize("prng,bits,B,boost", BODY_CASES)
@pytest.mark.parametrize("kernel", ["dense", "compact"])
def test_tile_body_branches_match_the_oracle(kernel, prng, bits, B, boost):
    """Each static branch of the tile body equals ``ref.ta_update_ref`` on
    two clause tiles and two literal tiles (the event tiles are expanded
    on a clause tile's first literal tile and reused on the next); the
    compact kernel gets one active clause tile of two."""
    C, L = 136, 300
    ta, lit, cl, t1, t2, lm = _ta_inputs(C, L, B, seed=B + bits)
    if kernel == "compact":
        t1, t2 = t1.at[:, 128:].set(0), t2.at[:, 128:].set(0)
    kw = dict(rand_bits=16, boost=boost, prng=prng, lfsr_bits=bits,
              seed_refresh=True)
    want = ref.ta_update_ref(ta, lit, cl, t1, t2, lm, 77, 7000, **kw)
    if kernel == "dense":
        got = ta_update_op(ta, lit, cl, t1, t2, lm, 77, 7000,
                           backend="pallas", **kw)
    else:
        got, inc = kops.ta_update_compact_op(ta, lit, cl, t1, t2, lm,
                                             ref.pack_include(ta, 256), 77,
                                             7000, backend="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(inc),
                                      np.asarray(ref.pack_include(want, 256)))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert not np.array_equal(np.asarray(want), np.asarray(ta))


def test_resolve_ta_prng_env(monkeypatch):
    for v, want in (("", "inkernel"), ("auto", "inkernel"),
                    ("inkernel", "inkernel"), ("stream", "stream")):
        monkeypatch.setenv("REPRO_TA_PRNG", v)
        assert resolve_ta_prng() == want
    monkeypatch.setenv("REPRO_TA_PRNG", "banana")
    with pytest.raises(ValueError):
        resolve_ta_prng()


# ---------------------------------------------------------------------------
# popcount-as-matmul packed eval
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eval_mode", [False, True])
def test_packed_mxu_matches_vpu(eval_mode):
    """MXU leg == VPU leg == both jnp oracles on a ragged literal count,
    with an all-exclude (empty) clause present to pin the fired/empty
    semantics either side of eval_mode."""
    B, C, L = 5, 40, 200
    rng = np.random.default_rng(3)
    lit = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int32)
    inc = jnp.asarray(rng.integers(0, 2, (C, L)), jnp.int32)
    inc = inc.at[7].set(0)                       # empty clause
    plit, pinc = ref.pack_bitplane(lit), ref.pack_bitplane(inc)
    want = ref.packed_clause_eval_ref(plit, pinc, eval_mode=eval_mode,
                                      n_bits=L)
    for name, got in [
        ("mxu_ref", ref.packed_clause_mxu_ref(plit, pinc,
                                              eval_mode=eval_mode,
                                              n_bits=L)),
        ("mxu_op_ref", packed_clause_mxu_op(plit, pinc, eval_mode=eval_mode,
                                            n_bits=L, backend="ref")),
        ("mxu_op_pallas", packed_clause_mxu_op(plit, pinc,
                                               eval_mode=eval_mode,
                                               n_bits=L, backend="pallas")),
        ("vpu_op", packed_clause_eval_op(plit, pinc, eval_mode=eval_mode,
                                         n_bits=L, backend="pallas")),
    ]:
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got),
                                      err_msg=name)


def test_packed_step_mxu_matches_vpu():
    """The packed training front half is path-invariant too: the mxu flag
    only changes HOW clause outputs are counted."""
    B, f, C, H = 8, 50, 32, 3
    L = 2 * f
    rng = np.random.default_rng(5)
    lit = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int8)
    inc = jnp.asarray(rng.integers(0, 2, (C, L)), jnp.int8)
    plit, pinc = ref.pack_bitplane(lit), ref.pack_bitplane(inc)
    w = jnp.asarray(rng.integers(-4, 5, (H, C)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, H, (B,)), jnp.int32)
    neg = (lab + 1) % H
    r1 = jnp.asarray(rng.integers(0, 1 << 16, (B, C)), jnp.uint32)
    r2 = jnp.asarray(rng.integers(0, 1 << 16, (B, C)), jnp.uint32)
    msk, hm = jnp.ones((C,), jnp.int32), jnp.ones((H,), jnp.int32)
    args = (w, lab, neg, r1, r2, msk, hm, 16, 0)
    for backend in ("ref", "pallas"):
        vpu = kops.packed_step_op(plit, pinc, *args, n_bits=L,
                                  backend=backend)
        mxu = kops.packed_step_op(plit, pinc, *args, n_bits=L,
                                  backend=backend, mxu=True)
        for a, b in zip(jax.tree.leaves(vpu), jax.tree.leaves(mxu)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=backend)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_resolve_autotune_env(monkeypatch):
    for v, want in (("", "seed"), ("auto", "seed"), ("off", "off"),
                    ("seed", "seed"), ("measure", "measure")):
        monkeypatch.setenv("REPRO_AUTOTUNE", v)
        assert autotune.resolve_autotune() == want
    monkeypatch.setenv("REPRO_AUTOTUNE", "banana")
    with pytest.raises(ValueError):
        autotune.resolve_autotune()


def test_seed_plan_dispatch(monkeypatch):
    """Seed plans re-route ONLY the throughput eval path (to the roofline
    winner); edge eval, training, and the TA stage keep the hand
    heuristics, so off vs seed agree everywhere else."""
    shape = (1024, 512, 8)
    # this test asserts the HEURISTIC/plan dispatch — a forced path from
    # the CI matrix leg (REPRO_KERNEL_PATH=packed_vpu) must not leak in
    monkeypatch.delenv("REPRO_KERNEL_PATH", raising=False)
    monkeypatch.setenv("REPRO_AUTOTUNE", "seed")
    autotune.clear_cache()
    assert select_path(None, batch=1, shape=shape) == kops.PATH_PACKED
    assert select_path(None, batch=256, shape=shape) == kops.PATH_PACKED_MXU
    assert select_path(None, batch=256, training=True,
                       shape=shape) == kops.PATH_FUSED
    assert kops.select_ta_path(shape=shape) == \
        kops.select_ta_path(shape=None)
    # no shape -> no plan consulted (engine-init backend resolution)
    assert select_path(None, batch=256) == kops.PATH_MXU
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert select_path(None, batch=256, shape=shape) == kops.PATH_MXU


def test_measure_mode_persists_plan(tmp_path, monkeypatch):
    """measure mode times the candidates once, persists the winner to the
    plan cache, and every later lookup (any mode but off) reuses it."""
    cache = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    autotune.clear_cache()
    shape = (64, 32, 4)
    plan = autotune.lookup("eval", 8, shape)
    assert plan is not None and plan["source"] == "measure"
    assert plan["path"] in (kops.PATH_PACKED, kops.PATH_PACKED_MXU,
                            kops.PATH_MXU)
    on_disk = json.loads(cache.read_text())
    assert autotune.plan_key("eval", 8, shape) in on_disk
    # a fresh process in seed mode picks the measured plan up from disk
    autotune.clear_cache()
    monkeypatch.setenv("REPRO_AUTOTUNE", "seed")
    again = autotune.lookup("eval", 8, shape)
    assert again == plan
    # off mode ignores it
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.lookup("eval", 8, shape) is None
    autotune.clear_cache()


def test_packed_eval_costs_roofline():
    from repro.launch.tm_perf import packed_eval_costs, ta_rand_bytes
    c = packed_eval_costs(256, 1024, 512)
    assert c["winner"] == "mxu_popcount"       # throughput regime
    assert c["mxu_s"] < c["vpu_s"]
    # the in-kernel PRNG's whole point, in bytes
    r = ta_rand_bytes(8, 1024, 512)
    assert r["streamed_rand_bytes"] == 8 * 512 * 1024 * 4
    assert r["inkernel_rand_bytes"] == 0


def test_peaks_keyed_by_device_kind():
    """The roofline reads the peaks row of the device in use: CPU runs
    rehearse with the v5e row by name, an accelerator kind without a row
    raises instead of borrowing another chip's peaks."""
    from types import SimpleNamespace
    from repro.launch.mesh import PEAKS, V5E, hardware_model
    assert hardware_model() is V5E                # this CPU host
    assert PEAKS["TPU v5 lite"] is V5E and V5E.source
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hardware_model(chip) is V5E
    with pytest.raises(ValueError, match="no peaks"):
        hardware_model(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v0 imaginary"))


def test_compile_cache_placement(tmp_path):
    """The compile cache goes where JAX_COMPILATION_CACHE_DIR put it (JAX
    reads the variable into jax.config), else to one fixed directory in
    the checkout; every compile is cached however quick."""
    from repro.launch import compile_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.config.update("jax_compilation_cache_dir", None)
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent == (
            pathlib.Path(__file__).resolve().parents[1])
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


# ---------------------------------------------------------------------------
# config-level prng_backend validation
# ---------------------------------------------------------------------------

def test_prng_backend_typo_raises():
    with pytest.raises(ValueError, match="prng_backend"):
        TMConfig(prng_backend="lsfr")
    with pytest.raises(ValueError, match="prng_backend"):
        TMSpec.coalesced(features=8, classes=2, clauses=8,
                         prng_backend="Threefry")
    # distributed lowering guards duck-typed configs too (TMConfig itself
    # can no longer be constructed with a typo)
    from repro.core import distributed

    class Bad:
        prng_backend = "lsfr"

    with pytest.raises(ValueError, match="prng_backend"):
        distributed._shard_prng(Bad(), 0, 0)


# ---------------------------------------------------------------------------
# engine-level bit-identity across every re-routing axis
# ---------------------------------------------------------------------------

def _train_once(kind, backend, monkeypatch, env=(), prng_backend=None):
    for k, v in env:
        monkeypatch.setenv(k, v)
    autotune.clear_cache()
    spec = SPECS[kind]
    if prng_backend is not None:
        import dataclasses
        spec = dataclasses.replace(spec, prng_backend=prng_backend)
    tm = api.TM(spec, seed=0, backend=backend)
    rng = np.random.default_rng(0)
    n = 16
    if kind == "conv":
        x = (rng.random((n, 6, 6)) < 0.4).astype(np.int8)
    elif kind == "head":
        x = rng.standard_normal((n, 8)).astype(np.float32)
    else:
        x = (rng.random((n, spec.features)) < 0.5).astype(np.int8)
    if kind == "regression":
        y = rng.random(n).astype(np.float32)
    else:
        y = rng.integers(0, spec.classes, n).astype(np.int32)
    hist = tm.fit(x, y, epochs=1, batch=8, rng=np.random.default_rng(3))
    for k, _ in env:
        monkeypatch.delenv(k, raising=False)
    autotune.clear_cache()
    return tm, hist


# every axis the kernel-speed pass can re-route through, vs one baseline
AXES = [
    ("stream", [("REPRO_TA_PRNG", "stream")]),
    ("autotune_off", [("REPRO_AUTOTUNE", "off")]),
    ("force_vpu", [("REPRO_KERNEL_PATH", "packed_vpu")]),
    ("force_mxu_popcount", [("REPRO_KERNEL_PATH", "mxu_popcount")]),
]


@pytest.mark.parametrize("kind", sorted(SPECS))
@pytest.mark.parametrize("prng_backend", ["counter", "lfsr"])
def test_engine_invariant_across_axes_ref(kind, prng_backend, monkeypatch):
    base_tm, base_h = _train_once(kind, "ref", monkeypatch,
                                  prng_backend=prng_backend)
    for name, env in AXES:
        tm, h = _train_once(kind, "ref", monkeypatch, env=env,
                            prng_backend=prng_backend)
        assert h == base_h, (name, kind)
        for l1, l0 in zip(jax.tree.leaves(tm.program),
                          jax.tree.leaves(base_tm.program)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0),
                                          err_msg=f"{kind}/{name}")
    fam = "lfsr" if prng_backend == "lfsr" else "counter"
    paths = base_tm.engine.cache_report()["path_per_stage"]
    key = "train_conv_ta_prng" if kind == "conv" else "train_prng"
    assert paths[key] == f"{fam}-inkernel"


@pytest.mark.parametrize("kind", ["cotm", "conv"])
def test_engine_invariant_across_axes_kernel(kind, monkeypatch):
    """Interpret-mode Pallas smoke for the same claim (full five-kind
    kernel matrix is the slow tier below)."""
    base_tm, base_h = _train_once(kind, "ref", monkeypatch,
                                  prng_backend="lfsr")
    for name, env in [("kernel", []),
                      ("kernel_stream", [("REPRO_TA_PRNG", "stream")]),
                      ("kernel_off", [("REPRO_AUTOTUNE", "off")])]:
        tm, h = _train_once(kind, "kernel", monkeypatch, env=env,
                            prng_backend="lfsr")
        assert h == base_h, (name, kind)
        for l1, l0 in zip(jax.tree.leaves(tm.program),
                          jax.tree.leaves(base_tm.program)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0),
                                          err_msg=f"{kind}/{name}")


@pytest.mark.slow
@pytest.mark.parametrize("kind", sorted(SPECS))
def test_engine_invariant_across_axes_kernel_full(kind, monkeypatch):
    base_tm, base_h = _train_once(kind, "ref", monkeypatch,
                                  prng_backend="lfsr")
    for name, env in [("kernel", [])] + AXES:
        tm, h = _train_once(kind, "kernel", monkeypatch, env=env,
                            prng_backend="lfsr")
        assert h == base_h, (name, kind)
        for l1, l0 in zip(jax.tree.leaves(tm.program),
                          jax.tree.leaves(base_tm.program)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0),
                                          err_msg=f"{kind}/{name}")
