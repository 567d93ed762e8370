"""The Conv CoTM against its plain reference (``bench/kinds/conv_ref.py``),
at a small size on the CPU.

* ``TM.fit`` (staging, the epoch scan) and single ``train_conv`` steps
  equal the reference bit for bit — TA states, weights, per-step stats,
  predictions — for both random families and both engine backends (the
  jnp oracles and the interpret-mode Pallas kernels);
* the patch a clause is updated against is one it fired on, the
  ``(u mod n)``-th of them;
* a conv step draws only small arrays from the PRNG: no scan in its
  jaxpr, or in its clause-sharded twin's, runs longer than the selection
  draw's ``ceil(2·B·R / lanes)`` cycles;
* the ``ta_update_conv`` kernel equals its oracle on ragged shapes and
  at a nonzero global row offset;
* chunked staging equals the one-shot encode.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import dtm
from repro.core.booleanize import pack_literals
from repro.core.prng import PRNG
from repro.kernels import ops

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import data  # noqa: E402
from kinds import conv  # noqa: E402

B, STEPS = 8, 3


def _config(prng: str = "lfsr") -> dict:
    """``mnist-convtm`` cut to 10x10 images, 4x4 windows (49 patches of
    28 features), 40 clauses and 4 classes, with the layout this size's
    engine has (``tile_for``: 128 literal columns, 128 clause rows)."""
    cfg = json.loads((BENCH / "configs" / "mnist-convtm.json").read_text())
    cfg.update(name="small-convtm", img_h=10, img_w=10, patch=4, clauses=40,
               classes=4, T=15, s=3.9, prng=prng)
    cfg["engine"] = dict(literal_columns=128, selection_rows=128,
                         classes_padded=8, negated_literal_column=64,
                         ta_row_stride=256, skip_group_rows=128,
                         draw_lanes=8192, patch_slots=49)
    cfg["dataset"].update(motif_size=3, strokes=2, stroke_length=3)
    return cfg


def test_small_config_layout_is_the_engines():
    cfg = _config()
    tile = api.tile_for(conv.spec(cfg))
    L, R, H = tile.padded_dims()
    e = cfg["engine"]
    assert (L, R, H) == (e["literal_columns"], e["selection_rows"],
                         e["classes_padded"])
    assert tile.max_patches == e["patch_slots"] == conv.sizes(cfg)[3]


@pytest.mark.parametrize("backend", ["ref", "kernel"])
@pytest.mark.parametrize("prng", ["lfsr", "counter"])
@pytest.mark.parametrize("seed", [5, 3000000019])
def test_fit_and_steps_equal_the_plain_reference(backend, prng, seed):
    cfg = _config(prng)
    spec = conv.spec(cfg)
    hp = conv.hyper(cfg)
    mot = conv.motifs(cfg)
    n = B * STEPS
    x, y = (np.asarray(a) for a in conv.rows(cfg, mot, seed, n))
    ta0, w0 = conv.program(cfg, mot, "paper_init", seed, 0)
    pseed = data.tenant_seed(seed, 0)

    # the normal path: TM.fit stages the images and runs one epoch scan
    tm = api.TM(spec, seed=pseed, backend=backend)
    eng = tm.engine
    tm.program = eng.lower(spec, jax.random.PRNGKey(0), ta=ta0, weights=w0)
    hist = tm.fit(x, y, epochs=1, batch=B, rng=np.random.default_rng(seed))
    idx = np.random.default_rng(seed).permutation(n)
    xs, ys = x[idx].reshape(STEPS, B, 10, 10), y[idx].reshape(STEPS, B)
    (r_ta, r_w, _), st = conv.train(hp, ta0, w0, pseed + 1, jnp.asarray(xs),
                                    jnp.asarray(ys))
    st = {k: np.asarray(v) for k, v in st.items()}
    p_ta, p_w = conv.unpad(cfg, tm.program.ta, tm.program.weights)
    assert np.array_equal(p_ta, np.asarray(r_ta))
    assert np.array_equal(p_w, np.asarray(r_w))
    assert not np.array_equal(p_ta, np.asarray(ta0, np.int32))
    rec = hist[0]
    assert rec["selected_clauses"] == int(st["selected"].sum())
    assert rec["active_groups"] == int(st["active_groups"].sum())
    assert round(rec["train_acc"] * n) == int(st["correct"].sum())
    want = np.asarray(conv.predict(hp, r_ta, r_w, jnp.asarray(x)))
    assert np.array_equal(np.asarray(tm.predict(x)), want)

    # single train_conv steps from the same start (the fit donated its
    # program): per-step stats too
    prog = eng.lower(spec, jax.random.PRNGKey(0), ta=ta0, weights=w0)
    rng = PRNG.create(spec.tm_config(), pseed + 1)
    for s in range(STEPS):
        prog, rng, stats = eng.train_conv(prog, rng,
                                          eng.encode(spec, xs[s]), ys[s])
        for k in ("selected", "active_groups", "correct"):
            assert int(stats[k]) == int(st[k][s]), (s, k)
    assert np.array_equal(conv.unpad(cfg, prog.ta, prog.weights)[0], p_ta)
    paths = eng.cache_report()["path_per_stage"]
    assert paths["train_conv_ta"] == ("ref" if backend == "ref"
                                      else "pallas")
    assert paths["train_conv_ta_prng"] == f"{prng}-inkernel"


@pytest.mark.parametrize("P", [1, 31, 32, 33, 361])
def test_the_chosen_patch_is_a_firing_one(P):
    rng = np.random.default_rng(P)
    Bn, R = 3, 64
    # per clause row a different firing density, some rows never fire
    fired = (rng.random((Bn, P, R)) < rng.random(R) ** 2 * 0.6).astype(
        np.int32)
    fired[:, :, :4] = 0
    u = rng.integers(0, 1 << 16, (Bn, R)).astype(np.uint32)
    got = np.asarray(dtm.choose_patch(jnp.asarray(fired), jnp.asarray(u)))
    for b in range(Bn):
        for r in range(R):
            hits = np.flatnonzero(fired[b, :, r])
            if hits.size:
                assert fired[b, got[b, r], r] == 1
                assert got[b, r] == hits[u[b, r] % hits.size]
            else:
                assert got[b, r] == 0


def _scan_lengths(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["length"]
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _scan_lengths(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _scan_lengths(sub)


@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_a_conv_step_draws_no_wide_random_tensor(backend):
    """With a 16-lane cluster the selection draw [2, B, R] takes
    ceil(2·B·R/16) = 128 cycles; a [B, P, R] patch draw would take 3,136
    and a [2, B, R, L] TA draw 16,384.  No scan of the step (the PRNG's,
    the TA update's batch loop, the kernel's) is longer than 128, and one
    step advances the cluster by the four small draws alone."""
    from repro.launch import pod
    from repro.launch.mesh import make_clause_mesh
    cfg = _config()
    spec = conv.spec(cfg)
    eng = api.compile(api.tile_for(spec), backend=backend)
    prog = eng.lower(spec, jax.random.PRNGKey(0))
    lanes = 16
    rng = PRNG.create(spec.tm_config(), 7, n_lanes=lanes)
    x = np.asarray(conv.rows(cfg, conv.motifs(cfg), 1, B)[0])
    lits, lab = eng.encode(spec, x), jnp.arange(B, dtype=jnp.int32) % 4
    bound = math.ceil(2 * B * eng.R / lanes)
    assert bound == 128
    lengths = list(_scan_lengths(jax.make_jaxpr(eng._train_conv_impl)(
        prog, rng, lits, lab).jaxpr))
    assert bound in lengths and max(lengths) <= bound, lengths
    stm = pod.ShardedTM(eng, make_clause_mesh(1), conv=True)
    sharded = list(_scan_lengths(jax.make_jaxpr(stm.train_step)(
        stm.shard(prog), rng, lits, lab).jaxpr))
    assert bound in sharded and max(sharded) <= bound, sharded
    if backend == "ref":
        _, after, _ = eng.train_conv(prog, rng, lits, lab)
        cycles = [math.ceil(n / lanes) for n in (B, 2 * B * eng.R, 2,
                                                 B * eng.R)]
        assert int(after.state.cycles) == sum(cycles) == 194


@pytest.mark.parametrize("C,L,Bl,row0,prng", [
    (40, 70, 3, 0, "counter"), (130, 300, 2, 7, "lfsr"),
    (128, 256, 4, 130, "lfsr"), (200, 384, 1, 0, "counter")])
def test_conv_kernel_equals_its_oracle(C, L, Bl, row0, prng):
    """Ragged clause and literal counts (padded to whole tiles inside
    ``ta_update_conv_op``) and a global row offset (a clause shard)."""
    rng = np.random.default_rng(C + L)
    W = -(-L // 32)
    ta = jnp.asarray(rng.integers(0, 256, (C, L)), jnp.uint8)
    bits = rng.integers(0, 2, (Bl, C, 32 * W)).astype(np.int8)
    bits[..., L:] = 0
    words = pack_literals(jnp.asarray(bits))
    E = 2 * Bl
    cl = jnp.asarray(rng.integers(0, 2, (E, C)), jnp.int32)
    t1 = jnp.asarray(rng.integers(0, 2, (E, C)), jnp.int32)
    t2 = jnp.asarray(rng.integers(0, 2, (E, C)), jnp.int32) * (1 - t1)
    l_mask = jnp.asarray(np.arange(L) % 7 != 3, jnp.int32)
    kw = dict(seed=jnp.uint32(90210), p_ta=jnp.uint32(6554), rand_bits=16,
              boost=bool(C % 2), n_states=256, row0=row0, prng=prng)
    want = ops.ta_update_conv_op(ta, words, cl, t1, t2, l_mask,
                                 backend="ref", **kw)
    got = ops.ta_update_conv_op(ta, words, cl, t1, t2, l_mask,
                                backend="pallas", **kw)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert not np.array_equal(np.asarray(want[0]), np.asarray(ta, np.int32))
    # with one literal row shared by every clause it is the flat update
    lits = jnp.asarray(bits[:, 0, :L])
    shared = jnp.broadcast_to(words[:, :1], words.shape)
    flat = ops.ta_update_op(ta, jnp.concatenate([lits, lits]), cl, t1, t2,
                            l_mask, backend="ref", emit_include=True, **kw)
    conv_ = ops.ta_update_conv_op(ta, shared, cl, t1, t2, l_mask,
                                  backend="ref", **kw)
    for f, c in zip(flat, conv_):
        assert np.array_equal(np.asarray(f), np.asarray(c))


@pytest.mark.parametrize("n,chunk", [(37, 8), (37, 37), (37, 64), (40, 8)])
def test_chunked_staging_equals_the_one_shot_encode(n, chunk, monkeypatch):
    monkeypatch.setattr(dtm, "STAGE_CHUNK_ROWS", chunk)
    cfg = _config()
    spec = conv.spec(cfg)
    eng = api.compile(api.tile_for(spec), backend="ref")
    x, y = (np.asarray(a) for a in conv.rows(cfg, conv.motifs(cfg), 2, n))
    session = eng.bind(eng.lower(spec, jax.random.PRNGKey(0)), spec=spec)
    session.stage(x, y)
    staged = np.asarray(session._lits)
    assert staged.shape == (n, eng.P, eng.W)
    assert np.array_equal(staged, np.asarray(eng.encode(spec, x)))
