"""Plain reference of the Convolutional Coalesced Tsetlin Machine: the
convolutional TM of Granmo et al. (arXiv:1905.09688) with the CoTM's
shared clause pool and signed class weights (arXiv:2504.19797).

It imports nothing of the program under test.  Its state is the model as
the papers state it, at the published sizes: TA states ``ta [C, 2F]``
over the literals of one patch (``f_0 .. f_{F-1}, ~f_0 .. ~f_{F-1}``)
and class weights ``w [H, C]``.  Arithmetic is int32 throughout, so every
result is exact and a comparison with the program is an equality.

Patches (Granmo et al. §3): a ``K x K`` window at stride 1 over an
``IH x IW`` Boolean image; the ``P = (IH-K+1)(IW-K+1)`` patches are in
row-major order of their upper-left corner ``(i, j)``.  A patch's ``F``
features are its window's bits in row-major order, then a thermometer
code of its position: ``IH-K`` bits ``i > t`` and ``IW-K`` bits ``j > t``.

Inference: a clause fires on a patch iff every literal it includes is 1
there, and on the image iff it fires on some patch (and, in inference,
includes a literal); class sums and prediction are the CoTM's.

One training step, the CoTM's (``coalesced_ref.train_step``) with the
convolution's feedback:

1. draws from the model's random source: one per datapoint for the
   negated class, ``2 x B x R`` for clause selection, two for the seed of
   the per-TA streams, then ``B x R`` patch-choice numbers, one per
   (datapoint, clause row).  The source is the LFSR cluster
   (``reference.py``) or, for a configuration whose ``prng`` is
   ``counter``, a counter stream: the ``i``-th number of a draw is
   ``splitmix32(c * 0x9E3779B1 ^ i)`` cut to ``rand_bits``, and ``c``
   (the seed, or 0xC0FFEE for 0) counts the draws;
2. clause outputs in training mode on every patch (an empty clause
   fires), OR over the patches, class sums;
3. clause selection for the target and the negated class, as the CoTM;
4. for each (datapoint, clause) the patch whose literals its feedback
   reads: one of the ``n`` patches on which the clause fired, at random
   (Granmo et al.), here the ``(u mod n)``-th in patch order, ``u`` its
   patch-choice number; patch 0 where none fired, since Type I and II
   then do not read the literals.  (This choice replaced a perturbed
   argmax over the patches, which was biased and could tie.)  Both
   feedback rounds of a datapoint use its one choice;
5. Type I / Type II feedback as the CoTM's against the chosen patch's
   literals, gated by the clause's OR-level output; each TA has its own
   random stream, keyed on the step seed and the TA's place in the
   engine's TA plane and advanced once per feedback round (all target
   rounds, then all negated rounds): an LFSR lane as in the flat
   reference, or for ``counter`` the chain ``s = splitmix32(seed ^ id)``,
   ``s = xorshift32(s)`` per round, cut to ``rand_bits``;
6. weights as the CoTM's.

The ``engine`` block of a configuration file gives the layout facts the
random streams depend on, as for the flat CoTM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kinds.coalesced_ref import class_sums, clip_weights
from reference import (cluster, draw, emit, lfsr_cycle, lfsr_seed,
                       splitmix32, xorshift32)

_U = jnp.uint32


# ---------------------------------------------------------- random source

def _source(hp: dict, seed: int):
    if hp["prng"] == "lfsr":
        return cluster(seed, hp["lanes"], hp["lfsr_bits"])
    return _U(seed if seed else 0xC0FFEE)


def _draw(hp: dict, st, size: int):
    """``size`` numbers of ``rand_bits`` bits from the model's source."""
    if hp["prng"] == "lfsr":
        return draw(st, size, hp["lfsr_bits"], hp["refresh"],
                    hp["rand_bits"])
    out = splitmix32(st * _U(0x9E3779B1) ^ jnp.arange(size, dtype=_U))
    return st + _U(1), out >> (32 - hp["rand_bits"])


def _ta_streams(hp: dict, seed, ids):
    """Each TA's stream at the start of a step."""
    if hp["prng"] == "lfsr":
        return lfsr_seed(seed, ids, hp["lfsr_bits"]), seed, _U(0)
    return (splitmix32(seed ^ ids),)


def _ta_next(hp: dict, st, ids):
    """Every TA's stream advanced once, with its number."""
    if hp["prng"] == "lfsr":
        lanes, master, cycles = lfsr_cycle(*st, ids, hp["lfsr_bits"],
                                           hp["refresh"])
        return ((lanes, master, cycles),
                emit(lanes, hp["lfsr_bits"], hp["rand_bits"]))
    s = xorshift32(st[0])
    return (s,), s >> (32 - hp["rand_bits"])


# ---------------------------------------------------------------- patches

def _geometry(hp: dict) -> tuple:
    K = hp["patch"]
    oh, ow = hp["img_h"] - K + 1, hp["img_w"] - K + 1
    i, j = np.divmod(np.arange(oh * ow), ow)                   # [P]
    di, dj = np.divmod(np.arange(K * K), K)                    # [K*K]
    rows = i[:, None] + di[None, :]
    cols = j[:, None] + dj[None, :]
    pos = np.concatenate([i[:, None] > np.arange(oh - 1)[None, :],
                          j[:, None] > np.arange(ow - 1)[None, :]], axis=1)
    return rows, cols, pos.astype(np.int32)


def literals(hp: dict, x: jax.Array) -> jax.Array:
    """Images x {0,1} [n, IH, IW] -> patch literals int32 [n, P, 2F]."""
    rows, cols, pos = _geometry(hp)
    n = x.shape[0]
    win = x.astype(jnp.int32)[:, rows, cols]                   # [n, P, K*K]
    f = jnp.concatenate(
        [win, jnp.broadcast_to(jnp.asarray(pos), (n, *pos.shape))], axis=-1)
    return jnp.concatenate([f, 1 - f], axis=-1)


def patch_outputs(include: jax.Array, lits: jax.Array) -> jax.Array:
    """include bool [C, 2F], lits int32 [n, P, 2F] -> int32 [n, P, C]:
    1 where the clause fires on the patch (training mode)."""
    n, P, L2 = lits.shape
    viol = jax.lax.dot_general(
        (1 - lits).reshape(n * P, L2).astype(jnp.int8),
        include.astype(jnp.int8), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (viol == 0).astype(jnp.int32).reshape(n, P, -1)


# ---------------------------------------------------------------- inference

@functools.partial(jax.jit, static_argnums=(0,))
def _predict(hp_items, ta, w, x):
    hp = dict(hp_items)
    include = ta.astype(jnp.int32) >= (1 << (hp["ta_bits"] - 1))
    cl = patch_outputs(include, literals(hp, x)).max(axis=1)
    cl = cl * include.any(axis=1)[None, :]
    sums = class_sums(cl, clip_weights(w, hp["weight_bits"]))
    return jnp.argmax(sums, axis=-1).astype(jnp.int32)


def predict(hp: dict, ta, w, x):
    """Predictions int32 [n] of one model (ta [C, 2F], w [H, C]) on images
    x [n, IH, IW]."""
    return _predict(tuple(sorted(hp.items())), ta, w, x)


# ---------------------------------------------------------------- training

def train_step(hp: dict, state, xb, yb):
    """One step of batch (xb int8 [B, IH, IW], yb int32 [B]) from
    ``state`` = (ta int32 [C, 2F], w int32 [H, C], cluster).  Returns
    (state, stats)."""
    ta, w, st = state
    B = xb.shape[0]
    C, H, R = hp["clauses"], hp["classes"], hp["rows"]
    T, rb = hp["T"], hp["rand_bits"]
    J = 1 << (hp["ta_bits"] - 1)
    st, c_rand = _draw(hp, st, B)
    st, sel_rand = _draw(hp, st, 2 * B * R)
    sel_rand = sel_rand.reshape(2, B, R)[:, :, :C].astype(jnp.int32)
    st, seed_bits = _draw(hp, st, 2)
    ta_seed = (seed_bits[0] << rb) | seed_bits[1]
    st, u = _draw(hp, st, B * R)
    u = u.reshape(B, R)[:, :C]

    rn = (c_rand % _U(max(H - 1, 1))).astype(jnp.int32)
    neg = jnp.where(rn < yb, rn, rn + 1)
    include = ta >= J
    lits = literals(hp, xb)                                    # [B, P, 2F]
    fired = patch_outputs(include, lits)                       # [B, P, C]
    cl = fired.max(axis=1)                                     # [B, C]
    sums = class_sums(cl, w)
    correct = (jnp.argmax(sums, -1) == yb).sum()

    def select(cls, target: bool, rand):
        v = jnp.clip(jnp.take_along_axis(sums, cls[:, None], 1), -T, T)
        p = T - v if target else T + v
        return (rand * (2 * T) < (p << rb)).astype(jnp.int32)

    sel_lab, sel_neg = select(yb, True, sel_rand[0]), select(neg, False,
                                                              sel_rand[1])

    # the (u mod n)-th of the n firing patches, in patch order (0 if none)
    n_fired = fired.sum(axis=1)                                # [B, C]
    k = (u % jnp.maximum(n_fired, 1).astype(_U)).astype(jnp.int32)
    rank = jnp.cumsum(fired, axis=1) - 1                       # [B, P, C]
    patch = jnp.argmax((fired > 0) & (rank == k[:, None, :]), axis=1)
    chosen = jnp.take_along_axis(lits, patch[:, :, None], axis=1)  # [B,C,2F]

    w_lab, w_neg = w[yb], w[neg]
    t1 = jnp.concatenate([sel_lab * (w_lab >= 0), sel_neg * (w_neg < 0)])
    t2 = jnp.concatenate([sel_lab * (w_lab < 0), sel_neg * (w_neg >= 0)])
    lits2 = jnp.concatenate([chosen, chosen])
    cl2 = jnp.concatenate([cl, cl])

    # per-TA lanes: id = row * stride + column of the literal in the plane
    F = lits.shape[-1] // 2
    j = jnp.arange(2 * F)
    col = jnp.where(j < F, j, hp["neg_col"] + j - F)
    ids = (jnp.arange(C, dtype=_U)[:, None] * _U(hp["stride"])
           + col.astype(_U)[None, :])
    p_ta = _U(hp["p_ta"])

    def feedback(carry, xs):
        streams, delta = carry
        lit, c, a, b = xs                                      # lit [C, 2F]
        streams, rand = _ta_next(hp, streams, ids)
        low = rand < p_ta
        both = (c[:, None] > 0) & (lit > 0)
        inc1 = both if hp["boost"] else both & ~low
        d1 = inc1.astype(jnp.int32) - (~both & low).astype(jnp.int32)
        d2 = (c[:, None] > 0) & (lit == 0) & ~include
        delta = (delta + jnp.where(a[:, None] > 0, d1, 0)
                 + jnp.where(b[:, None] > 0, d2.astype(jnp.int32), 0))
        return (streams, delta), None

    start = (_ta_streams(hp, ta_seed, ids), jnp.zeros((C, 2 * F), jnp.int32))
    (_, delta), _ = jax.lax.scan(feedback, start, (lits2, cl2, t1, t2))
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
    if cfg["prng"] not in ("lfsr", "counter"):
        raise ValueError(f"reference draws from the lfsr cluster or a "
                         f"counter stream, not {cfg['prng']!r}")
    e = cfg["engine"]
    hp = dict(clauses=cfg["clauses"], classes=cfg["classes"], T=cfg["T"],
              ta_bits=cfg["ta_bits"], weight_bits=cfg["weight_bits"],
              rand_bits=cfg["rand_bits"], prng=cfg["prng"],
              lfsr_bits=cfg["lfsr_bits"], refresh=cfg["seed_refresh"],
              boost=cfg["boost_true_positive"],
              p_ta=int(round((1 << cfg["rand_bits"]) / cfg["s"])),
              img_h=cfg["img_h"], img_w=cfg["img_w"], patch=cfg["patch"],
              rows=e["selection_rows"], neg_col=e["negated_literal_column"],
              stride=e["ta_row_stride"], group=e["skip_group_rows"],
              lanes=e["draw_lanes"])
    hp.update(override)
    return hp


@functools.partial(jax.jit, static_argnums=(0,))
def _train(hp_items, state, xs, ys):
    hp = dict(hp_items)
    return jax.lax.scan(lambda s, b: train_step(hp, s, *b), state, (xs, ys))


def train(hp: dict, ta, w, prng_seed: int, xs, ys):
    """Steps over batches xs [S, B, IH, IW], ys [S, B] from (ta, w) and a
    fresh random source seeded ``prng_seed``, one step at a time (a
    scan).  Returns ((ta, w, source), stats [S])."""
    state = (ta.astype(jnp.int32), w.astype(jnp.int32),
             _source(hp, prng_seed))
    return _train(tuple(sorted(hp.items())), state, xs, ys)
