"""Clause-skip execution (ISSUE 5 acceptance).

The Alg-6 compacted TA-update datapath must be a pure wall-clock
optimisation — never a semantic one:

* ops-level: ``ta_update_compact_op`` == ``ta_update_op(emit_include=True)``
  bit-for-bit on BOTH backends (jnp ref + interpret-mode Pallas sparse
  kernel), under random feedback masks (hypothesis property when
  available + a deterministic sweep), on remainder shapes, at every
  capacity-bucket boundary (n_active == cap and cap + 1), and at row /
  tile compaction granularities;
* engine-level: training with ``REPRO_SKIP=1`` (compact) vs
  ``REPRO_SKIP=0`` (dense-forced) produces bit-identical programs,
  histories, and stats for all FIVE TMSpec kinds on both backends — this
  file runs under both ``REPRO_KERNEL_PATH`` CI legs like the rest of the
  suite;
* session-level: the in-trace capacity switch keeps the device-resident
  epoch scan at ≤ 1 dispatch per epoch (``session.dispatches`` probe),
  and program banks fall back to the dense update (vmap would otherwise
  execute every bucket per lane);
* observability: ``path_per_stage`` records the SKIP dimension
  (``train_ta`` = compact/dense) and ``TMServer.stats()`` surfaces the
  per-tenant lifetime ``skip_frac``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import TMSpec
from repro.core import PRNG
from repro.kernels import (ops as kops, ref, resolve_skip, select_ta_path,
                           ta_update_compact_op, ta_update_op)

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                                     # bare tier-1 env
    hypothesis = None

_rng = np.random.default_rng(7)
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


# ---------------------------------------------------------------------------
# ops-level bit-identity: compact == dense
# ---------------------------------------------------------------------------

def _inputs(C, L, B, active_rows, seed=0, n_states=256):
    rng = np.random.default_rng(seed)
    ta = jnp.asarray(rng.integers(0, n_states, (C, L)), jnp.int32)
    lit = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int8)
    cl = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
    t1 = jnp.asarray(rng.integers(0, 2, (B, C)) * active_rows[None, :],
                     jnp.int8)
    t2 = jnp.asarray(rng.integers(0, 2, (B, C)) * active_rows[None, :],
                     jnp.int8)
    lm = jnp.asarray(rng.integers(0, 2, (L,)), jnp.int32)
    inc = ref.pack_include(ta, n_states)
    return ta, lit, cl, t1, t2, lm, inc


def _assert_compact_equals_dense(C, L, B, active_rows, backend, group,
                                 seed=0, n_states=256):
    ta, lit, cl, t1, t2, lm, inc = _inputs(C, L, B, active_rows, seed,
                                           n_states)
    s, p = jnp.uint32(seed * 77 + 5), jnp.uint32(16000)
    d_ta, d_inc = ta_update_op(ta, lit, cl, t1, t2, lm, s, p,
                               backend=backend, emit_include=True,
                               n_states=n_states)
    c_ta, c_inc = ta_update_compact_op(ta, lit, cl, t1, t2, lm, inc, s, p,
                                       backend=backend, group=group,
                                       n_states=n_states)
    np.testing.assert_array_equal(np.asarray(d_ta), np.asarray(c_ta))
    np.testing.assert_array_equal(np.asarray(d_inc), np.asarray(c_inc))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("shape", [(256, 512, 4), (200, 300, 3),
                                   (64, 40, 2)])
def test_compact_matches_dense_sweep(backend, shape):
    """Deterministic sweep: both backends, remainder shapes, activity from
    empty to full."""
    C, L, B = shape
    rng = np.random.default_rng(C)
    for frac in (0.0, 0.05, 0.3, 1.0):
        act = (rng.random(C) < frac).astype(np.int8)
        _assert_compact_equals_dense(C, L, B, act, backend,
                                     group=1 if backend == "ref" else 32,
                                     seed=int(frac * 10))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_capacity_bucket_boundaries(backend):
    """n_active exactly AT each capacity bucket and one past it (the
    lax.switch branch edges), including the dense-fallback edge."""
    C, L, B, group = 256, 128, 2, (1 if backend == "ref" else 128)
    n_groups = -(-C // group)
    caps = kops._skip_caps(n_groups)
    assert caps, (n_groups, group)
    for cap in caps:
        for n_act in (max(cap - 1, 0), cap, min(cap + 1, n_groups)):
            act = np.zeros(C, np.int8)
            # scatter the active groups non-contiguously
            gidx = np.linspace(0, n_groups - 1, max(n_act, 1),
                               dtype=int)[:n_act]
            for gi in gidx:
                act[gi * group:(gi + 1) * group] = 1
            _assert_compact_equals_dense(C, L, B, act, backend, group,
                                         seed=cap + n_act)


def test_compact_row_vs_tile_granularity_agree():
    """The compaction granularity is an execution detail: row-level (the
    engine's ref path) and coarse-group compaction produce the same
    bits."""
    C, L, B = 192, 96, 3
    rng = np.random.default_rng(0)
    act = (rng.random(C) < 0.1).astype(np.int8)
    for group in (1, 8, 32, 64):
        _assert_compact_equals_dense(C, L, B, act, "ref", group)


# ---------------------------------------------------------------------------
# PRNG stream invariants (ISSUE 8): the TA-update randoms are a pure
# function of (seed, element index, stream family) — execution layout
# (dense / compact / streamed / banked) must never change them
# ---------------------------------------------------------------------------

def test_lfsr_stream_period_and_refresh():
    """With refresh off, the L-bit LFSR lanes are maximal-length: the
    emitted stream repeats with period 2^L - 1.  With the paper's master-
    slave refresh on, the cycle AT the period boundary is re-seeded from
    the advanced master instead of repeating."""
    bits, C, L = 4, 2, 8
    period = (1 << bits) - 1
    free = np.asarray(ref.ta_rand_stream(5, 2 * period, C, L, prng="lfsr",
                                         lfsr_bits=bits, seed_refresh=False,
                                         xt=L))
    np.testing.assert_array_equal(free[:period], free[period:])
    rr = np.asarray(ref.ta_rand_stream(5, period, C, L, prng="lfsr",
                                       lfsr_bits=bits, seed_refresh=True,
                                       xt=L))
    np.testing.assert_array_equal(rr[:period - 1], free[:period - 1])
    assert (rr[period - 1] != free[period - 1]).any()


def test_bank_lanes_identical_streams_lfsr():
    """lanes > 1 banks fall back to the dense TA update — under the
    paper-faithful lfsr family each lane must still advance exactly the
    per-program stream, so bank training == sequential per-program
    training bit-for-bit."""
    import dataclasses
    spec = dataclasses.replace(SPECS["cotm"], prng_backend="lfsr")
    eng = api.compile(api.tile_for(spec, x=32, y=16, m=16, n=4))
    progs, prngs = [], []
    for i in range(3):
        progs.append(eng.lower(spec, jax.random.PRNGKey(i)))
        prngs.append(PRNG.create(spec.tm_config(), 10 + i))
    rng = np.random.default_rng(0)
    x = (rng.random((3, 8, spec.features)) < 0.5).astype(np.int8)
    y = rng.integers(0, spec.classes, (3, 8)).astype(np.int32)
    lits = jnp.stack([eng.encode(spec, jnp.asarray(x[k]))
                      for k in range(3)])
    bank = api.stack(progs, eng, prngs=prngs)
    bank.train(lits, jnp.asarray(y))
    for k in range(3):
        solo, _, _ = eng.train_step(progs[k], prngs[k], lits[k],
                                    jnp.asarray(y[k]))
        got = bank.swap_out(k)
        np.testing.assert_array_equal(np.asarray(got.ta),
                                      np.asarray(solo.ta), err_msg=str(k))


if hypothesis is not None:
    @settings(max_examples=15, deadline=None)
    @given(st.data())
    def test_ta_stream_invariant_property(data):
        """Random shapes, seeds, families, refresh settings: the dense
        in-kernel stream == the Alg-6 compact path == the streamed
        [B, C, L] materialisation (ref backend; the Pallas legs are
        pinned by the deterministic sweeps in test_kernel_speed.py)."""
        C = data.draw(st.integers(2, 40), label="C")
        L = data.draw(st.integers(2, 64), label="L")
        B = data.draw(st.integers(1, 4), label="B")
        bits = data.draw(st.sampled_from((4, 8, 24)), label="bits")
        refresh = data.draw(st.booleans(), label="refresh")
        prng = data.draw(st.sampled_from(("counter", "lfsr")), label="prng")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        rng = np.random.default_rng(seed % 251)
        ta = jnp.asarray(rng.integers(0, 256, (C, L)), jnp.int32)
        lit = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int8)
        cl = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
        t1 = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
        t2 = jnp.asarray(rng.integers(0, 2, (B, C)), jnp.int8)
        lm = jnp.ones((L,), jnp.int32)
        inc = ref.pack_include(ta, 256)
        kw = dict(prng=prng, lfsr_bits=bits, seed_refresh=refresh,
                  backend="ref")
        dense = ta_update_op(ta, lit, cl, t1, t2, lm, seed, 9000, **kw)
        streamed = ta_update_op(ta, lit, cl, t1, t2, lm, seed, 9000,
                                stream=True, **kw)
        compact, _ = ta_update_compact_op(ta, lit, cl, t1, t2, lm, inc,
                                          seed, 9000, **kw)
        np.testing.assert_array_equal(np.asarray(dense),
                                      np.asarray(streamed))
        np.testing.assert_array_equal(np.asarray(dense),
                                      np.asarray(compact))

    @settings(max_examples=20, deadline=None)
    @given(st.data())
    def test_compact_matches_dense_property(data):
        """Random shapes, random (sparse to dense) feedback masks, random
        n_states — compact == dense bit-for-bit on the ref backend (the
        Pallas leg is pinned by the deterministic sweep; interpret-mode
        hypothesis sweeps are nightly-tier slow)."""
        C = data.draw(st.integers(2, 80), label="C")
        L = data.draw(st.integers(2, 70), label="L")
        B = data.draw(st.integers(1, 5), label="B")
        frac = data.draw(st.floats(0, 1), label="frac")
        group = data.draw(st.sampled_from((1, 4, 32)), label="group")
        n_states = data.draw(st.sampled_from((4, 256)), label="n_states")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        rng = np.random.default_rng(seed)
        act = (rng.random(C) < frac).astype(np.int8)
        _assert_compact_equals_dense(C, L, B, act, "ref", group,
                                     seed=seed % 97, n_states=n_states)


# ---------------------------------------------------------------------------
# engine-level: five kinds, skip on == skip off, both backends
# ---------------------------------------------------------------------------

def _train_once(kind, backend, skip, monkeypatch, epochs=2):
    monkeypatch.setenv("REPRO_SKIP", skip)
    spec = SPECS[kind]
    tm = api.TM(spec, seed=0, backend=backend)
    rng = np.random.default_rng(0)
    n = 48
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
    hist = tm.fit(x, y, epochs=epochs, batch=8,
                  rng=np.random.default_rng(3))
    return tm, hist


@pytest.mark.parametrize("kind", sorted(SPECS))
def test_engine_skip_bit_identical_ref(kind, monkeypatch):
    tm1, h1 = _train_once(kind, "ref", "1", monkeypatch)
    tm0, h0 = _train_once(kind, "ref", "0", monkeypatch)
    assert h1 == h0
    for leaf1, leaf0 in zip(jax.tree.leaves(tm1.program),
                            jax.tree.leaves(tm0.program)):
        np.testing.assert_array_equal(np.asarray(leaf1), np.asarray(leaf0))
    if kind != "conv":
        # the skip dimension is recorded (and differs between the runs)
        assert tm1.engine.cache_report()["path_per_stage"]["train_ta"] == \
            kops.TA_COMPACT
        assert tm0.engine.cache_report()["path_per_stage"]["train_ta"] == \
            kops.TA_DENSE
    else:
        # the conv TA update is the dense ta_update_conv stage either way;
        # it records its backend, the oracle here
        for tm in (tm1, tm0):
            assert tm.engine.cache_report()["path_per_stage"][
                "train_conv_ta"] == "ref"
    # lifetime skip accounting agrees between the two execution modes
    assert tm1.skip_frac == tm0.skip_frac
    assert tm1.skip_frac is not None


@pytest.mark.slow
@pytest.mark.parametrize("kind", sorted(SPECS))
def test_engine_skip_bit_identical_kernel(kind, monkeypatch):
    """Same claim through the interpret-mode Pallas kernels (the sparse
    scalar-prefetch gather kernel on the compact branch)."""
    tm1, h1 = _train_once(kind, "kernel", "1", monkeypatch, epochs=1)
    tm0, h0 = _train_once(kind, "kernel", "0", monkeypatch, epochs=1)
    assert h1 == h0
    for leaf1, leaf0 in zip(jax.tree.leaves(tm1.program),
                            jax.tree.leaves(tm0.program)):
        np.testing.assert_array_equal(np.asarray(leaf1), np.asarray(leaf0))


# ---------------------------------------------------------------------------
# sessions, banks, serving
# ---------------------------------------------------------------------------

def test_session_dispatches_stay_one_per_epoch_with_skip(monkeypatch):
    """The capacity-bucket selection is IN-TRACE (lax.switch inside the
    epoch scan): skip execution must not add host round trips."""
    monkeypatch.setenv("REPRO_SKIP", "1")
    spec = SPECS["cotm"]
    tm = api.TM(spec, seed=0)
    rng = np.random.default_rng(0)
    x = (rng.random((64, spec.features)) < 0.5).astype(np.int8)
    y = rng.integers(0, spec.classes, 64).astype(np.int32)
    session = tm.engine.bind(tm.program, x, y, spec=spec, prng=tm.prng)
    epochs = 3
    session.fit_epochs(epochs, batch=8, rng=np.random.default_rng(1))
    assert session.dispatches == epochs
    assert tm.engine.cache_report()["path_per_stage"]["train_ta"] == \
        kops.TA_COMPACT
    report = tm.engine.cache_report()
    assert all(v <= 1 for v in report.values() if isinstance(v, int)), report


def test_bank_training_forces_dense(monkeypatch):
    """vmapped program banks must take the dense TA path (lanes > 1) —
    and still match per-program sequential training bit-for-bit."""
    monkeypatch.setenv("REPRO_SKIP", "1")
    spec = SPECS["cotm"]
    eng = api.compile(api.tile_for(spec, x=32, y=16, m=16, n=4))
    progs, prngs = [], []
    for i in range(3):
        progs.append(eng.lower(spec, jax.random.PRNGKey(i)))
        prngs.append(PRNG.create(spec.tm_config(), 10 + i))
    rng = np.random.default_rng(0)
    x = (rng.random((3, 8, spec.features)) < 0.5).astype(np.int8)
    y = rng.integers(0, spec.classes, (3, 8)).astype(np.int32)
    lits = jnp.stack([eng.encode(spec, jnp.asarray(x[k]))
                      for k in range(3)])
    bank = api.stack(progs, eng, prngs=prngs)
    bank.train(lits, jnp.asarray(y))
    assert eng.cache_report()["path_per_stage"]["train_bank_ta"] == \
        kops.TA_DENSE
    for k in range(3):
        solo_prog, _, _ = eng.train_step(progs[k], prngs[k], lits[k],
                                         jnp.asarray(y[k]))
        got = bank.swap_out(k)
        np.testing.assert_array_equal(np.asarray(got.ta),
                                      np.asarray(solo_prog.ta))
        np.testing.assert_array_equal(np.asarray(got.inc),
                                      np.asarray(solo_prog.inc))


def test_server_surfaces_per_tenant_skip_frac(monkeypatch):
    monkeypatch.setenv("REPRO_SKIP", "1")
    from repro.launch.serve_tm import TMServer
    spec = SPECS["cotm"]
    eng = api.compile(api.tile_for(spec, x=32, y=16, m=16, n=4))
    server = TMServer(eng, batch_slot=8)
    server.register("a", spec)
    server.register("b", spec, seed=5)
    rng = np.random.default_rng(0)
    x = (rng.random((8, spec.features)) < 0.5).astype(np.int8)
    y = rng.integers(0, spec.classes, 8).astype(np.int32)
    stats = server.stats()
    assert stats["skip_frac"] == {"a": None, "b": None}
    for _ in range(3):
        server.train("a", x, y)
    frac = server.stats()["skip_frac"]
    assert frac["b"] is None
    assert frac["a"] is not None and 0.0 <= frac["a"] <= 1.0


def test_resolve_skip_env(monkeypatch):
    for v, want in (("", True), ("auto", True), ("1", True), ("0", False),
                    ("off", False)):
        monkeypatch.setenv("REPRO_SKIP", v)
        assert resolve_skip() is want
    monkeypatch.setenv("REPRO_SKIP", "banana")
    with pytest.raises(ValueError):
        resolve_skip()
    monkeypatch.setenv("REPRO_SKIP", "1")
    assert select_ta_path() == kops.TA_COMPACT
    assert select_ta_path(lanes=4) == kops.TA_DENSE
    monkeypatch.setenv("REPRO_SKIP", "0")
    assert select_ta_path() == kops.TA_DENSE
