"""The comparison that decides ``correct``, at a size a test run holds.

* the plain reference agrees with the program exactly (the sound runs);
* the control, the reference one precision step below the stated one
  (8-bit instead of 12-bit weights for serving, 8-bit instead of 16-bit
  random comparisons for training) put in the program's place, is not
  correct;
* a run whose timed path is broken underneath comes out not correct, for
  each fault a cell can have on one chip: an answer altered where it is
  produced, a training step that returns its state unchanged, half of a
  training batch left out.
"""
from __future__ import annotations

import io
import json

import jax
import numpy as np
import pytest

import harness

from conftest import tiny_config

CELLS = ["cotm-edge-b1-zipf", "kws6-batch-b32", "cotm-fit-b32"]
TRAINING = ["cotm-fit-b32"]


def run_cell(root, name, seed=7, seconds=1.5):
    out, err = io.StringIO(), io.StringIO()
    args = harness.parse(["--workload", name, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"])
    assert harness.run(args, 0.0, root=root, require_chip=False, out=out,
                       err=err) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_root, name):
    res = run_cell(tiny_root, name)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert all(c["value"] == 0 for c in res["compared"].values())


def test_training_reference_follows_the_program_exactly():
    import kinds
    from repro import api
    from repro.core.prng import PRNG
    cfg = tiny_config()
    kind = kinds.of(cfg)
    spec = kind.spec(cfg)
    eng = api.compile(api.tile_for(spec), backend="ref")
    mot = kind.motifs(cfg)
    ta, w = kind.program(cfg, mot, "paper_init", 5, 0)
    x, y = kind.rows(cfg, mot, 5, 4 * 8)
    prog = eng.lower(spec, jax.random.PRNGKey(0), ta=ta, weights=w)
    prng = PRNG.create(spec.tm_config(), 99)
    for s in range(4):
        prog, prng, _ = eng.train_step(
            prog, prng, eng.encode(spec, x[8 * s:8 * s + 8]),
            spec.encode_labels(y[8 * s:8 * s + 8]))
    (r_ta, r_w, _), _ = kind.train(kind.hyper(cfg), ta, w, 99,
                                   x.reshape(4, 8, -1), y.reshape(4, 8))
    p_ta, p_w = kind.unpad(cfg, prog.ta, prog.weights)
    assert np.array_equal(p_ta, np.asarray(r_ta))
    assert np.array_equal(p_w, np.asarray(r_w))
    assert not np.array_equal(p_ta, np.asarray(ta, np.int32))


def test_inference_reference_matches_the_program():
    import kinds
    from repro import api
    cfg = tiny_config()
    kind = kinds.of(cfg)
    spec = kind.spec(cfg)
    eng = api.compile(api.tile_for(spec), backend="ref")
    mot = kind.motifs(cfg)
    ta, w = kind.program(cfg, mot, "trained_like", 3, 1)
    x, _ = kind.rows(cfg, mot, 3, 64)
    prog = eng.lower(spec, jax.random.PRNGKey(0), ta=ta, weights=w)
    got = np.asarray(eng.predict(prog, eng.encode(spec, x)))
    want = np.asarray(kind.predict(kind.hyper(cfg), ta, w, x))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny_root, name):
    """The reference one precision step down (8-bit weights where the
    cell only serves, 8-bit random comparisons where it trains) in the
    program's place: a number compared reads above its limit."""
    d = harness.load_cell(name, tiny_root)
    reading = harness.control_readings(d["config"], d["traffic"], seed=7,
                                       seconds=1.5)
    assert any(reading["control"][k] > harness.LIMITS[k]
               for k in reading["control"]), reading
    assert all(v <= harness.LIMITS[k] for k, v in reading["sound"].items())


def _alter_answers(monkeypatch):
    from repro.launch.serve_tm import TMServer
    collect = TMServer.collect

    def altered(self, pf):
        return {k: np.asarray(v) + 1 for k, v in collect(self, pf).items()}

    monkeypatch.setattr(TMServer, "collect", altered)


def _unchanged_state(monkeypatch):
    from repro.core.dtm import DTMEngine
    impl = DTMEngine._train_impl

    def unchanged(self, prog, prng, plits, labels, lanes=1, stage="train"):
        _, _, stats = impl(self, prog, prng, plits, labels, lanes, stage)
        return prog, prng, stats

    monkeypatch.setattr(DTMEngine, "_train_impl", unchanged)


def _half_batch(monkeypatch):
    from repro.core.dtm import DTMEngine
    impl = DTMEngine._train_impl

    def half(self, prog, prng, plits, labels, lanes=1, stage="train"):
        h = plits.shape[0] // 2
        return impl(self, prog, prng, plits[:h], labels[:h], lanes, stage)

    monkeypatch.setattr(DTMEngine, "_train_impl", half)


FAULTS = ([(c, "answer", _alter_answers) for c in CELLS[:2]]
          + [(c, f.__name__, f) for c in TRAINING
             for f in (_unchanged_state, _half_batch)])


@pytest.mark.parametrize("name,fault,plant", FAULTS,
                         ids=[f"{c}-{f}" for c, f, _ in FAULTS])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, name,
                                            fault, plant):
    jax.clear_caches()
    plant(monkeypatch)
    res = run_cell(tiny_root, name, seed=11)
    jax.clear_caches()
    assert res["correct"] is False, res["compared"]
