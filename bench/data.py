"""Inputs and tenant programs of a run, made from ``--seed``.

Everything here is the benchmark's own: the program under test receives
only the arrays it returns.

* Rows follow the synthetic stand-in for the paper's datasets (each class
  a union of sparse bit motifs; a row switches on some of its class's
  motifs over background noise, then flips a few bits).  The motifs are
  the task's identity (``task_seed`` in the configuration file); the rows
  come from the run's seed.  Every call is one jitted program on the
  default device.
* Served programs (``trained_like``) have the clause structure of a
  trained CoTM: each clause includes part of one motif of its class plus
  a few negated background features, so that clauses fire on the rows
  they were shaped for; TA states spread over the whole 2^ta_bits range
  on the right side of the include threshold.  Their weights are random
  over the whole signed ``weight_bits`` range, so that a prediction
  depends on every bit of the class sums (with the class's own clauses
  weighted positive, the sign of each weight alone would decide nearly
  every prediction, and 8-bit weights would pass as 12-bit ones).  A
  program from ``paper_init`` is the paper's training start instead (TA
  at J-1 or J, weights +-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, *salt: int) -> int:
    """A 32-bit seed derived from ``seed`` (any size) and a salt."""
    return int(np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0])


def key(seed: int, *salt: int) -> jax.Array:
    return jax.random.PRNGKey(seed32(seed, *salt))


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


def program(cfg: dict, mot: jax.Array, kind: str, seed: int, tenant: int):
    """(TA states [C, 2F], weights [H, C]) of one tenant, on device."""
    k = key(seed, 2, tenant)
    if kind == "trained_like":
        return _served_program(k, mot, cfg["clauses"], cfg["ta_bits"],
                               cfg["weight_bits"],
                               cfg["dataset"]["neg_includes"])
    if kind == "paper_init":
        return _paper_init(k, cfg["clauses"], cfg["classes"],
                           2 * cfg["features"], cfg["ta_bits"])
    raise ValueError(f"unknown program kind {kind!r}")


def tenant_seed(seed: int, tenant: int) -> int:
    """The seed the program derives a tenant's random stream from (kept
    below 2^30 so every seed argument of the program takes it)."""
    return seed32(seed, 3, tenant) >> 2
