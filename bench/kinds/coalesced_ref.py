"""Plain reference of the Coalesced Tsetlin Machine, written from the paper.

It imports nothing of the program under test.  Its state is the model as
the paper states it, at the published sizes: TA states ``ta [C, 2F]``
(literal order ``x_0 .. x_{F-1}, ~x_0 .. ~x_{F-1}``) and class weights
``w [H, C]``.  Arithmetic is int32 throughout, so every result is exact
and a comparison with the program is an equality.

Inference (paper Eq. 1-2): a clause fires iff every literal it includes
is 1 and it includes at least one literal; a class sum is the weighted
sum of the firing clauses; the prediction is the first class of largest
sum.

One training step (paper Alg. 3-5, the batched-delta form: every TA
delta of the batch is computed from the states at the start of the step,
summed, and clipped once):

1. random draws from the master-slave LFSR cluster (paper Fig. 8,
   ``reference.py``): one per datapoint for the negated class,
   ``2 x B x R`` for clause selection (``R`` is the clause-row count the
   random numbers are laid out on), two for the seed of the per-TA
   streams;
2. clause outputs in training mode (an empty clause fires) and class
   sums;
3. clause selection for the target class (probability ``(T - clip(v))
   / 2T``) and for the negated class (``(T + clip(v)) / 2T``), compared
   in ``rand_bits`` fixed point;
4. Type I feedback where the clause's weight for that class is positive
   (target round) or negative (negated round), Type II otherwise; each
   TA has its own LFSR lane, seeded from the step seed and the TA's
   place in the engine's row-major TA plane, advanced once per feedback
   round (all target rounds, then all negated rounds);
5. weights move by +1 (target) / -1 (negated) per selected firing clause
   and are clipped to the signed ``weight_bits`` range.

The ``engine`` block of a configuration file gives the only layout facts
the random streams depend on: the clause rows the selection draws are
laid out on, the position of the negated literals in a TA row, and the
row stride of the TA plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import cluster, draw, emit, lfsr_cycle, lfsr_seed

_U = jnp.uint32


# ---------------------------------------------------------------- inference

def clause_outputs(include: jax.Array, x: jax.Array, eval_mode: bool):
    """include bool [C, 2F], x int8 {0,1} [n, F] -> int32 [n, C]."""
    lits = jnp.concatenate([x, 1 - x], axis=1).astype(jnp.int8)
    viol = jax.lax.dot_general(
        (1 - lits).astype(jnp.int8), include.astype(jnp.int8),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    fired = viol == 0
    if eval_mode:
        fired = fired & include.any(axis=1)[None, :]
    return fired.astype(jnp.int32)


def class_sums(cl: jax.Array, w: jax.Array) -> jax.Array:
    """cl int32 [n, C], w int32 [H, C] -> int32 [n, H] (exact, no MXU)."""
    return jnp.sum(cl[:, None, :] * w[None, :, :], axis=-1)


def clip_weights(w: jax.Array, weight_bits: int) -> jax.Array:
    lim = (1 << (weight_bits - 1)) - 1
    return jnp.clip(w, -lim, lim)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _predict(ta, w, x, ta_bits: int, weight_bits: int):
    include = ta.astype(jnp.int32) >= (1 << (ta_bits - 1))
    sums = class_sums(clause_outputs(include, x, True),
                      clip_weights(w, weight_bits))
    return jnp.argmax(sums, axis=-1).astype(jnp.int32)


def predict(hp: dict, ta, w, x):
    """Predictions int32 [n] of one model (ta [C, 2F], w [H, C]) on rows
    x [n, F].  ``hp["weight_bits"]`` below the model's own reads the
    weights at that width (the lower-precision control)."""
    return _predict(ta, w, x, hp["ta_bits"], hp["weight_bits"])


# ---------------------------------------------------------------- training

def train_step(hp: dict, state, xb, yb):
    """One step of batch (xb int8 [B, F], yb int32 [B]) from ``state`` =
    (ta int32 [C, 2F], w int32 [H, C], cluster).  ``hp`` holds the static
    hyper-parameters (see :func:`hyper`).  Returns (state, stats)."""
    ta, w, st = state
    B, F = xb.shape
    C, H, R = hp["clauses"], hp["classes"], hp["rows"]
    T, rb, lb = hp["T"], hp["rand_bits"], hp["lfsr_bits"]
    J = 1 << (hp["ta_bits"] - 1)
    st, c_rand = draw(st, B, lb, hp["refresh"], rb)
    st, sel_rand = draw(st, 2 * B * R, lb, hp["refresh"], rb)
    sel_rand = sel_rand.reshape(2, B, R)[:, :, :C].astype(jnp.int32)
    st, seed_bits = draw(st, 2, lb, hp["refresh"], rb)
    ta_seed = (seed_bits[0] << rb) | seed_bits[1]

    rn = (c_rand % _U(max(H - 1, 1))).astype(jnp.int32)
    neg = jnp.where(rn < yb, rn, rn + 1)
    include = ta >= J
    lits = jnp.concatenate([xb, 1 - xb], axis=1).astype(jnp.int32)
    cl = clause_outputs(include, xb, False)                        # [B, C]
    sums = class_sums(cl, w)                                       # [B, H]
    correct = (jnp.argmax(sums, -1) == yb).sum()

    def select(cls, target: bool, rand):
        v = jnp.clip(jnp.take_along_axis(sums, cls[:, None], 1), -T, T)
        p = T - v if target else T + v
        return (rand * (2 * T) < (p << rb)).astype(jnp.int32)

    sel_lab, sel_neg = select(yb, True, sel_rand[0]), select(neg, False,
                                                              sel_rand[1])
    w_lab, w_neg = w[yb], w[neg]
    t1 = jnp.concatenate([sel_lab * (w_lab >= 0), sel_neg * (w_neg < 0)])
    t2 = jnp.concatenate([sel_lab * (w_lab < 0), sel_neg * (w_neg >= 0)])
    lits2 = jnp.concatenate([lits, lits])
    cl2 = jnp.concatenate([cl, cl])

    # per-TA lanes: id = row * stride + column of the literal in the plane
    j = jnp.arange(2 * F)
    col = jnp.where(j < F, j, hp["neg_col"] + j - F)
    ids = (jnp.arange(C, dtype=_U)[:, None] * _U(hp["stride"])
           + col.astype(_U)[None, :])
    p_ta = _U(hp["p_ta"])

    def feedback(carry, xs):
        lanes, master, cycles, delta = carry
        lit, c, a, b = xs
        lanes, master, cycles = lfsr_cycle(lanes, master, cycles, ids, lb,
                                           hp["refresh"])
        low = emit(lanes, lb, rb) < p_ta
        both = (c[:, None] > 0) & (lit[None, :] > 0)
        inc1 = both if hp["boost"] else both & ~low
        d1 = inc1.astype(jnp.int32) - (~both & low).astype(jnp.int32)
        d2 = ((c[:, None] > 0) & (lit[None, :] == 0) & ~include)
        delta = (delta + jnp.where(a[:, None] > 0, d1, 0)
                 + jnp.where(b[:, None] > 0, d2.astype(jnp.int32), 0))
        return (lanes, master, cycles, delta), None

    start = (lfsr_seed(ta_seed, ids, lb), ta_seed, _U(0),
             jnp.zeros((C, 2 * F), jnp.int32))
    (_, _, _, delta), _ = jax.lax.scan(feedback, start, (lits2, cl2, t1, t2))
    ta = jnp.clip(ta + delta, 0, (1 << hp["ta_bits"]) - 1)

    lab_oh = (yb[:, None] == jnp.arange(H)[None, :]).astype(jnp.int32)
    neg_oh = (neg[:, None] == jnp.arange(H)[None, :]).astype(jnp.int32)
    d_w = (jnp.sum(lab_oh[:, :, None] * (sel_lab * cl)[:, None, :], 0)
           - jnp.sum(neg_oh[:, :, None] * (sel_neg * cl)[:, None, :], 0))
    w = clip_weights(w + d_w, hp["weight_bits"])

    d_sel = (sel_lab + sel_neg).sum(0)
    g = hp["group"]
    groups = jnp.pad(d_sel, (0, (-C) % g)).reshape(-1, g).max(-1) > 0
    stats = {"selected": d_sel.sum(), "active_groups": groups.sum(),
             "correct": correct}
    return (ta, w, st), stats


def hyper(cfg: dict, **override) -> dict:
    """Static hyper-parameters of :func:`predict` and :func:`train_step`
    from a configuration file (``override`` changes one for a control)."""
    e = cfg["engine"]
    hp = dict(clauses=cfg["clauses"], classes=cfg["classes"], T=cfg["T"],
              ta_bits=cfg["ta_bits"], weight_bits=cfg["weight_bits"],
              rand_bits=cfg["rand_bits"], lfsr_bits=cfg["lfsr_bits"],
              refresh=cfg["seed_refresh"], boost=cfg["boost_true_positive"],
              p_ta=int(round((1 << cfg["rand_bits"]) / cfg["s"])),
              rows=e["selection_rows"], neg_col=e["negated_literal_column"],
              stride=e["ta_row_stride"], group=e["skip_group_rows"],
              lanes=e["draw_lanes"])
    if cfg["prng"] != "lfsr":
        raise ValueError(f"reference draws only the lfsr cluster, not "
                         f"{cfg['prng']!r}")
    hp.update(override)
    return hp


@functools.partial(jax.jit, static_argnums=(0,))
def _train(hp_items, state, xs, ys):
    hp = dict(hp_items)
    return jax.lax.scan(lambda s, b: train_step(hp, s, *b), state, (xs, ys))


def train(hp: dict, ta, w, prng_seed: int, xs, ys):
    """Steps over batches xs [S, B, F], ys [S, B] from (ta, w) and a fresh
    cluster seeded ``prng_seed``.  Returns ((ta, w, cluster), stats [S])."""
    state = (ta.astype(jnp.int32), w.astype(jnp.int32),
             cluster(prng_seed, hp["lanes"], hp["lfsr_bits"]))
    return _train(tuple(sorted(hp.items())), state, xs, ys)
