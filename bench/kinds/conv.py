"""The Convolutional Coalesced Tsetlin Machine: the kind module of a
configuration of ``C`` clauses over the ``P`` patches of a Boolean image,
``H`` classes, one shared clause pool and signed class weights.

It keeps the contract ``kinds/coalesced.py`` states, with these readings:

* sizes: a ``K x K`` window at stride 1 over ``IH x IW`` images gives
  ``P = (IH-K+1)(IW-K+1)`` patches of ``F = K² + (IH-K) + (IW-K)``
  features (window bits and a thermometer code of the position), ``2F``
  literals;
* ``rows(cfg, mot, seed, n, salt=0)``: ``(x, y)`` with ``x`` int8 {0,1}
  images ``[n, IH, IW]``, as ``api.TM.fit`` takes conv input;
* ``program(...)``: only ``paper_init`` (the conv kind serves no cell);
* reference: ``conv_ref.py``.

Data.  A seeded synthetic stand-in for MNIST: each class has a few 2-D
stroke motifs (short straight strokes in 8 directions on an ``m x m``
canvas, made from ``task_seed``); an image of a class places some of its
class's motifs at random offsets, over background noise, then flips a
few pixels.  A motif fits a window at several offsets, so a clause that
learns it fires on several patches of an image and the patch choice of
the feedback matters.

Work, counted like the flat CoTM's (a multiply and an add are two):

* inference, per row: clause evaluation on every patch, ``2·C·2F·P``,
  plus class sums ``2·C·H`` (392,808,000 at MNIST geometry);
* training, per row: the inference operations plus one update of every
  (clause, literal) TA cell, ``C·2F``;
* ``clause_eval(cfg, rows, requests)``: ops ``2·C·2F·P·rows``; bytes
  ``requests·C·2F/8`` (the include plane once per launch) ``+
  rows·P·2F/8`` (every patch's literals) ``+ rows·P·C/8`` (one clause bit
  per clause and patch);
* ``ta_update(cfg, rows, steps, active_share)``: ops
  ``C·2F·rows·active_share``; bytes ``2·C·2F·steps·active_share`` (every
  TA state of the groups that received feedback, read and written once
  per step, ``ceil(ta_bits/8)`` bytes each) ``+ rows·P·2F/8`` (the
  patches the chosen literals come from) ``+ 4·rows·C`` (one patch index
  per row and clause).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from data import key
from kinds.coalesced import _paper_init
from kinds.conv_ref import hyper, predict, train  # noqa: F401


# ------------------------------------------------------------ program side

def spec(cfg: dict):
    from repro import api
    return api.TMSpec.conv(
        img_h=cfg["img_h"], img_w=cfg["img_w"], patch=cfg["patch"],
        classes=cfg["classes"], clauses=cfg["clauses"], T=cfg["T"],
        s=cfg["s"], ta_bits=cfg["ta_bits"], weight_bits=cfg["weight_bits"],
        rand_bits=cfg["rand_bits"], prng_backend=cfg["prng"],
        lfsr_bits=cfg["lfsr_bits"], seed_refresh=cfg["seed_refresh"],
        boost_true_positive=cfg["boost_true_positive"])


def unpad(cfg: dict, ta, w) -> tuple:
    """A program's TA plane and weights at the published sizes."""
    ta, w = np.asarray(ta).astype(np.int32), np.asarray(w)
    C, L2, H, _ = sizes(cfg)
    F = L2 // 2
    half = cfg["engine"]["negated_literal_column"]
    return (np.concatenate([ta[:C, :F], ta[:C, half:half + F]], axis=1),
            w[:H, :C])


# -------------------------------------------------------------------- data

_DIRS = ((0, 1), (1, 0), (1, 1), (1, -1), (0, -1), (-1, 0), (-1, -1),
         (-1, 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _motifs(k, classes: int, motifs: int, size: int, strokes: int,
            length: int):
    ks, kd = jax.random.split(k)
    start = jax.random.randint(ks, (classes, motifs, strokes, 2), 0, size)
    d = jnp.asarray(_DIRS)[jax.random.randint(kd, (classes, motifs, strokes),
                                              0, len(_DIRS))]
    pts = (start[..., None, :]
           + jnp.arange(length)[:, None] * d[..., None, :])   # [H,M,S,l,2]
    inside = ((pts >= 0) & (pts < size)).all(-1)
    g = jnp.arange(size)
    hit = ((pts[..., 0, None, None] == g[:, None])
           & (pts[..., 1, None, None] == g[None, :]) & inside[..., None, None])
    return hit.any(axis=(2, 3))                                # [H,M,m,m]


def motifs(cfg: dict) -> jax.Array:
    d = cfg["dataset"]
    return _motifs(jax.random.PRNGKey(d["task_seed"]), cfg["classes"],
                   d["motifs_per_class"], d["motif_size"], d["strokes"],
                   d["stroke_length"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _rows(k, mot, n: int, active: int, background_p: float, flip_p: float,
          img_h: int, img_w: int):
    ky, ka, ki, kj, kb, kf = jax.random.split(k, 6)
    H, M, m, _ = mot.shape
    y = jax.random.randint(ky, (n,), 0, H)
    u = jax.random.uniform(ka, (n, M))
    act = jnp.argsort(jnp.argsort(u, axis=-1), axis=-1) < active   # [n, M]
    oi = jax.random.randint(ki, (n, M), 0, img_h - m + 1)
    oj = jax.random.randint(kj, (n, M), 0, img_w - m + 1)
    blank = jnp.zeros((img_h, img_w), bool)

    def place(motif, i, j):
        return jax.lax.dynamic_update_slice(blank, motif, (i, j))

    img = jnp.zeros((n, img_h, img_w), bool)
    for s in range(M):
        img = img | jax.vmap(place)(mot[y, s] & act[:, s, None, None],
                                    oi[:, s], oj[:, s])
    img = img | (jax.random.uniform(kb, img.shape) < background_p)
    img = img ^ (jax.random.uniform(kf, img.shape) < flip_p)
    return img.astype(jnp.int8), y.astype(jnp.int32)


def rows(cfg: dict, mot: jax.Array, seed: int, n: int, salt: int = 0):
    """``n`` images (int8 {0,1} [n, IH, IW]) and labels (int32 [n]) on
    device."""
    d = cfg["dataset"]
    return _rows(key(seed, 1, salt), mot, n, d["active_motifs"],
                 d["background_p"], d["flip_p"], cfg["img_h"],
                 cfg["img_w"])


def program(cfg: dict, mot: jax.Array, programs: str, seed: int,
            tenant: int):
    """(TA states [C, 2F], weights [H, C]) of one tenant, on device: the
    paper's training start (``paper_init``: TA at J-1 or J, weights
    +-1)."""
    if programs != "paper_init":
        raise ValueError(f"the conv kind has no {programs!r} programs "
                         "(it serves no cell); use paper_init")
    C, L2, H, _ = sizes(cfg)
    return _paper_init(key(seed, 2, tenant), C, H, L2, cfg["ta_bits"])


# -------------------------------------------------------------------- work

def sizes(cfg: dict) -> tuple:
    """(C, 2F, H, P) at the published geometry."""
    K, ih, iw = cfg["patch"], cfg["img_h"], cfg["img_w"]
    F = K * K + (ih - K) + (iw - K)
    return cfg["clauses"], 2 * F, cfg["classes"], (ih - K + 1) * (iw - K + 1)


def infer_ops_per_row(cfg: dict) -> int:
    C, L2, H, P = sizes(cfg)
    return 2 * C * L2 * P + 2 * C * H


def train_ops_per_row(cfg: dict) -> int:
    C, L2, _, _ = sizes(cfg)
    return infer_ops_per_row(cfg) + C * L2


def clause_eval(cfg: dict, rows: int, requests: int) -> dict:
    C, L2, _, P = sizes(cfg)
    return {"ops": 2 * C * L2 * P * rows,
            "bytes": (requests * C * L2 / 8 + rows * P * L2 / 8
                      + rows * P * C / 8)}


def ta_update(cfg: dict, rows: int, steps: int, active_share: float) -> dict:
    C, L2, _, P = sizes(cfg)
    state = -(-cfg["ta_bits"] // 8)
    return {"ops": C * L2 * rows * active_share,
            "bytes": (2 * state * C * L2 * steps * active_share
                      + rows * P * L2 / 8 + 4 * rows * C)}
