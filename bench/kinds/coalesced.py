"""The Coalesced Tsetlin Machine (CoTM): the kind module of a flat
configuration of ``C`` clauses over ``F`` Boolean features and ``H``
classes, with one shared clause pool and signed class weights.

The contract every ``kinds/<kind>.py`` keeps.  Each function takes the
configuration dict; the harness, the control, the metric readers and the
work counts reach the kind only through these:

* program side: ``spec(cfg)``, the ``api.TMSpec`` that the normal path
  (``api.TM.fit``, ``api.serve`` / ``TMScheduler``) runs;
  ``unpad(cfg, ta, w)``, a program's state at the published sizes;
* data, made from ``--seed``: ``motifs(cfg)``, the task's identity;
  ``rows(cfg, mot, seed, n, salt=0)``, ``(x, y)`` as the normal path
  takes raw input (here ``[n, F]`` bits); ``program(cfg, mot, programs,
  seed, tenant)``, one tenant's ``(ta, w)`` at the published sizes;
* reference, in a file beside the module that imports nothing of the
  program (here ``coalesced_ref.py``): ``hyper(cfg, **override)``, the
  static hyper-parameters (``override`` sets the control's lower
  precision); ``predict(hp, ta, w, x)``; ``train(hp, ta, w, prng_seed,
  xs, ys)`` over batches ``xs [S, B, ...]``, returning ``((ta, w, _),
  stats)`` with per-step ``selected``, ``active_groups`` and ``correct``;
* work, of the algorithm at the published sizes, never the engine's
  padded geometry: ``infer_ops_per_row(cfg)``, ``train_ops_per_row(cfg)``,
  ``clause_eval(cfg, rows, requests)`` and ``ta_update(cfg, rows, steps,
  active_share)``, the last two as ``{"ops", "bytes"}`` for
  ``work.roofline_s``.

Shared pieces stay shared: the LFSR cluster (``reference.py``), the
peaks and the roofline (``work.py``), and the seeds (``data.py``).

Data.  Rows follow the synthetic stand-in for the paper's datasets (each
class a union of sparse bit motifs; a row switches on some of its class's
motifs over background noise, then flips a few bits).  The motifs are
the task's identity (``task_seed`` in the configuration file); the rows
come from the run's seed.  Every call is one jitted program on the
default device.  Served programs (``trained_like``) have the clause
structure of a trained CoTM: each clause includes part of one motif of
its class plus a few negated background features, so that clauses fire
on the rows they were shaped for; TA states spread over the whole
2^ta_bits range on the right side of the include threshold.  Their
weights are random over the whole signed ``weight_bits`` range, so that a
prediction depends on every bit of the class sums (with the class's own
clauses weighted positive, the sign of each weight alone would decide
nearly every prediction, and 8-bit weights would pass as 12-bit ones).
A program from ``paper_init`` is the paper's training start instead (TA
at J-1 or J, weights +-1).

Work.  Operations are counted the way the int8 peak counts them (a
multiply and an add are two):

* inference, per row: clause evaluation 2·C·2F (one include-and-literal
  test and one accumulate per TA) plus class sums 2·C·H;
* training, per row: the inference operations plus one update of every
  (clause, literal) TA cell, C·2F (the two feedback rounds of a row are
  summed per cell before the clip, so a cell is written once per row).
  Recomputed work does not count.

Bytes are the least a stage must move through HBM:

* clause evaluation: the served model's include plane once per request
  (C·2F bits), the row's literals (2F bits) and one clause bit per
  clause and row (C/8 bytes);
* TA update: every TA state of the clause groups that received feedback,
  read and written once per step (``ceil(ta_bits/8)`` bytes a state);
  its operations too count only those groups' cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from data import key
from kinds.coalesced_ref import hyper, predict, train  # noqa: F401


# ------------------------------------------------------------ program side

def spec(cfg: dict):
    from repro import api
    return api.TMSpec.coalesced(
        features=cfg["features"], classes=cfg["classes"],
        clauses=cfg["clauses"], T=cfg["T"], s=cfg["s"],
        ta_bits=cfg["ta_bits"], weight_bits=cfg["weight_bits"],
        rand_bits=cfg["rand_bits"], prng_backend=cfg["prng"],
        lfsr_bits=cfg["lfsr_bits"], seed_refresh=cfg["seed_refresh"],
        boost_true_positive=cfg["boost_true_positive"])


def unpad(cfg: dict, ta, w) -> tuple:
    """A program's TA plane and weights at the published sizes."""
    ta, w = np.asarray(ta).astype(np.int32), np.asarray(w)
    C, F, H = cfg["clauses"], cfg["features"], cfg["classes"]
    half = cfg["engine"]["negated_literal_column"]
    return (np.concatenate([ta[:C, :F], ta[:C, half:half + F]], axis=1),
            w[:H, :C])


# -------------------------------------------------------------------- data

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _motifs(k, classes: int, motifs: int, features: int, bits: int):
    u = jax.random.uniform(k, (classes, motifs, features))
    rank = jnp.argsort(jnp.argsort(u, axis=-1), axis=-1)
    return rank < bits                                   # [H, M, F] bool


def motifs(cfg: dict) -> jax.Array:
    d = cfg["dataset"]
    return _motifs(jax.random.PRNGKey(d["task_seed"]), cfg["classes"],
                   d["motifs_per_class"], cfg["features"], d["motif_bits"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _rows(k, mot, n: int, active: int, background_p: float, flip_p: float,
          classes: int):
    ky, ka, kb, kf = jax.random.split(k, 4)
    H, M, F = mot.shape
    y = jax.random.randint(ky, (n,), 0, classes)
    u = jax.random.uniform(ka, (n, M))
    act = jnp.argsort(jnp.argsort(u, axis=-1), axis=-1) < active   # [n, M]
    on = jnp.zeros((n, F), bool)
    for m in range(M):
        on = on | (act[:, m, None] & mot[y, m])
    on = on | (jax.random.uniform(kb, (n, F)) < background_p)
    on = on ^ (jax.random.uniform(kf, (n, F)) < flip_p)
    return on.astype(jnp.int8), y.astype(jnp.int32)


def rows(cfg: dict, mot: jax.Array, seed: int, n: int, salt: int = 0):
    """``n`` rows (int8 {0,1} [n, F]) and labels (int32 [n]) on device."""
    d = cfg["dataset"]
    return _rows(key(seed, 1, salt), mot, n, d["active_motifs"],
                 d["background_p"], d["flip_p"], cfg["classes"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _served_program(k, mot, clauses: int, ta_bits: int, weight_bits: int,
                    neg_includes: int):
    H, M, F = mot.shape
    kc, kp, kn, kt, kw = jax.random.split(k, 5)
    cls = jnp.arange(clauses) % H
    m = jax.random.randint(kc, (clauses,), 0, M)
    row = mot[cls, m]                                               # [C, F]
    pos = row & jax.random.bernoulli(kp, 0.5, (clauses, F))
    neg = ~row & jax.random.bernoulli(kn, neg_includes / F, (clauses, F))
    inc = jnp.concatenate([pos, neg], axis=1)                       # [C, 2F]
    J = 1 << (ta_bits - 1)
    kt1, kt2 = jax.random.split(kt)
    ta = jnp.where(inc, jax.random.randint(kt1, inc.shape, J, 2 * J),
                   jax.random.randint(kt2, inc.shape, 0, J))
    wmax = (1 << (weight_bits - 1)) - 1
    w = jax.random.randint(kw, (H, clauses), -wmax, wmax + 1)
    return ta.astype(jnp.uint8 if ta_bits <= 8 else jnp.int32), w.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _paper_init(k, clauses: int, classes: int, literals: int, ta_bits: int):
    kt, kw = jax.random.split(k)
    J = 1 << (ta_bits - 1)
    ta = J - 1 + jax.random.bernoulli(kt, 0.5, (clauses, literals)).astype(jnp.int32)
    w = jnp.where(jax.random.bernoulli(kw, 0.5, (classes, clauses)), 1, -1)
    return ta.astype(jnp.uint8 if ta_bits <= 8 else jnp.int32), w.astype(jnp.int32)


def program(cfg: dict, mot: jax.Array, programs: str, seed: int,
            tenant: int):
    """(TA states [C, 2F], weights [H, C]) of one tenant, on device;
    ``programs`` is the traffic file's ``trained_like`` or
    ``paper_init``."""
    k = key(seed, 2, tenant)
    if programs == "trained_like":
        return _served_program(k, mot, cfg["clauses"], cfg["ta_bits"],
                               cfg["weight_bits"],
                               cfg["dataset"]["neg_includes"])
    if programs == "paper_init":
        return _paper_init(k, cfg["clauses"], cfg["classes"],
                           2 * cfg["features"], cfg["ta_bits"])
    raise ValueError(f"unknown programs {programs!r}")


# -------------------------------------------------------------------- work

def sizes(cfg: dict) -> tuple:
    return cfg["clauses"], 2 * cfg["features"], cfg["classes"]


def infer_ops_per_row(cfg: dict) -> int:
    C, L2, H = sizes(cfg)
    return 2 * C * L2 + 2 * C * H


def train_ops_per_row(cfg: dict) -> int:
    C, L2, _ = sizes(cfg)
    return infer_ops_per_row(cfg) + C * L2


def clause_eval(cfg: dict, rows: int, requests: int) -> dict:
    C, L2, _ = sizes(cfg)
    return {"ops": 2 * C * L2 * rows,
            "bytes": requests * C * L2 / 8 + rows * L2 / 8 + rows * C / 8}


def ta_update(cfg: dict, rows: int, steps: int, active_share: float) -> dict:
    C, L2, _ = sizes(cfg)
    state = -(-cfg["ta_bits"] // 8)
    return {"ops": C * L2 * rows * active_share,
            "bytes": 2 * state * C * L2 * steps * active_share}
