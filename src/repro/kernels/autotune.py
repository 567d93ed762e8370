"""Measured TileConfig / kernel-path autotuner (the dispatch layer's plan
cache).

``select_path`` / ``select_ta_path`` historically picked datapaths from
hand-tuned thresholds (PACKED_MAX_BATCH et al.).  This module gives them a
per-(device_kind, stage, batch-bucket, shape) PLAN consulted first, with
the heuristics as the universal fallback:

* ``REPRO_AUTOTUNE=off``     — heuristics only (the CI parity leg);
* ``REPRO_AUTOTUNE=seed``    — (default) plans seeded from the
  launch/tm_perf analytic roofline, computed in-memory and deterministic:
  no timing, no disk writes, same answer on every host.  A measured plan
  already on disk for this device kind takes precedence;
* ``REPRO_AUTOTUNE=measure`` — candidates (path × tile geometry ×
  skip-capacity bucket) are TIMED on the live device with synthetic
  inputs at the workload's padded shape, and the winning plan is
  persisted to the on-disk cache, so every later process (any mode but
  ``off``) reuses it.

Plan cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``<checkout>/.autotune/autotune_<device_kind>.json`` (git-ignored; nothing
the dispatcher reads comes from outside the checkout) — one file per
device kind,
keyed ``stage/b<batch-bucket>/L..xR..xH..`` (batch buckets are
next-power-of-2, so nearby batch sizes share a plan).  Regenerate on new
hardware by deleting the file and running any workload (or
``benchmarks/autotune_bench.py``) under ``REPRO_AUTOTUNE=measure``.

Everything here runs at Python dispatch level (path selection happens
before the jitted ops are entered), so measure-mode timing uses ordinary
wall clocks and never traces.
"""
from __future__ import annotations

import json
import os
import pathlib
import time

MODES = ("off", "seed", "measure")

# dispatch stages with plans: inference clause eval, training front half,
# TA update (the SKIP dimension).
STAGES = ("eval", "train", "ta")

# Tile-geometry candidates swept by measure mode, per stage.  Ops pad
# every operand to tile multiples, and every tile is (8, 128)-aligned —
# Mosaic's block rule; a packed-word tile wider than the row becomes the
# whole row (ops.lane_tile) — so all geometries are legal for any shape.
EVAL_TILES = ({"bt": 8, "yt": 128, "wt": 128},
              {"bt": 8, "yt": 128, "wt": 256})
TRAIN_TILES = ({"bt": 8, "yt": 128, "xt": 256},)
TA_TILES = ({"yt": 128, "xt": 256},)

_MEASURE_ITERS = 5

# process-level plan state: _DISK is the lazily-loaded on-disk cache
# (None = not read yet), _MEM holds plans measured in this process.
_DISK: dict | None = None
_MEM: dict = {}


def resolve_autotune() -> str:
    """Single source of truth for the autotune mode (``REPRO_AUTOTUNE``)."""
    env = os.environ.get("REPRO_AUTOTUNE", "seed").strip().lower()
    if env in ("", "auto"):
        return "seed"
    if env not in MODES:
        raise ValueError(
            f"REPRO_AUTOTUNE={env!r} not recognised; use one of {MODES}")
    return env


# default plan-file directory: fixed, inside the checkout
PLAN_DIR = pathlib.Path(__file__).resolve().parents[3] / ".autotune"


def device_kind() -> str:
    """Plan-cache namespace: the JAX device kind (e.g. ``TPU_v5_lite``),
    ``cpu`` under interpret mode."""
    import jax
    return jax.devices()[0].device_kind.replace(" ", "_")


def cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE", "").strip()
    if env:
        return pathlib.Path(env)
    return PLAN_DIR / f"autotune_{device_kind()}.json"


def clear_cache() -> None:
    """Drop the in-process plan state (tests; does not touch the disk)."""
    global _DISK, _MEM
    _DISK = None
    _MEM = {}


def _bucket(batch) -> int:
    """Next-power-of-2 batch bucket; 0 = unknown (throughput default)."""
    if batch is None:
        return 0
    b = 1
    while b < batch:
        b *= 2
    return b


def plan_key(stage: str, batch, shape) -> str:
    L, R, H = shape
    return f"{stage}/b{_bucket(batch)}/L{L}xR{R}xH{H}"


def _disk_plans() -> dict:
    global _DISK
    if _DISK is None:
        try:
            _DISK = json.loads(cache_path().read_text())
        except FileNotFoundError:
            _DISK = {}
    return _DISK


def _persist(key: str, plan: dict) -> None:
    plans = dict(_disk_plans())
    plans[key] = plan
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(plans, indent=1, sort_keys=True))
    global _DISK
    _DISK = plans


def lookup(stage: str, batch, shape, lanes: int = 1) -> dict | None:
    """The plan for (stage, batch bucket, shape) under the current mode:
    ``{"path": <name>, "tiles": {...}, "source": seed|measure}`` or None
    (= no plan; caller falls back to heuristics).  Measured plans (this
    process or the disk cache) always outrank the roofline seed."""
    mode = resolve_autotune()
    if mode == "off" or shape is None:
        return None
    key = plan_key(stage, batch, shape)
    plan = _MEM.get(key) or _disk_plans().get(key)
    if plan is not None:
        return plan
    if mode == "measure":
        plan = _measure_plan(stage, batch, shape)
        if plan is not None:
            _MEM[key] = plan
            _persist(key, plan)
        return plan
    return _seed_plan(stage, batch, shape, lanes)


def planned_path(stage: str, batch, shape, lanes: int = 1) -> str | None:
    plan = lookup(stage, batch, shape, lanes)
    return None if plan is None else plan["path"]


def planned_tiles(stage: str, batch, shape) -> dict | None:
    plan = lookup(stage, batch, shape)
    return None if plan is None else plan.get("tiles")


# ---------------------------------------------------------------------------
# seed mode — the tm_perf roofline decides, nothing is timed or written
# ---------------------------------------------------------------------------

def _seed_plan(stage: str, batch, shape, lanes: int = 1) -> dict | None:
    from . import ops
    from ..launch import tm_perf
    L, R, H = shape
    B = _bucket(batch) or 256          # unknown batch: throughput regime
    if stage == "eval":
        if batch is not None and batch <= ops.PACKED_MAX_BATCH:
            path = ops.PATH_PACKED     # edge regime: keep the VPU word path
        else:
            # same packed bytes either way; the roofline picks the engine
            # (mxu_popcount from B ≳ VPU-lane-width up — 8x fewer HBM
            # bytes than the dense-literal mxu matmul it displaces)
            path = tm_perf.packed_eval_costs(B, L, R)["winner"]
        return {"path": path, "tiles": dict(EVAL_TILES[0]),
                "source": "seed"}
    if stage == "train":
        # the roofline agrees with the hand heuristics here (fused saves
        # the clause-matrix round trip; packed wins the edge regime) —
        # seeding them keeps off/seed parity exact for training.
        if batch is not None and batch <= ops.PACKED_MAX_BATCH:
            path = ops.PATH_PACKED
        else:
            path = ops.PATH_FUSED
        return {"path": path, "tiles": dict(TRAIN_TILES[0]),
                "source": "seed"}
    if stage == "ta":
        return None                    # select_ta_path heuristics hold
    raise ValueError(f"unknown autotune stage {stage!r}; use {STAGES}")


# ---------------------------------------------------------------------------
# measure mode — time the candidates on the live device, persist the winner
# ---------------------------------------------------------------------------

def _time(fn) -> float:
    """Median wall-clock seconds of a blocking thunk (after one warmup)."""
    fn()
    ts = []
    for _ in range(_MEASURE_ITERS):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _measure_plan(stage: str, batch, shape) -> dict | None:
    import jax.numpy as jnp
    import numpy as np
    from . import ops, ref
    L, R, H = shape
    B = max(_bucket(batch), 1)
    rng = np.random.default_rng(0)
    lits = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.int32)
    inc = jnp.asarray(rng.integers(0, 2, (R, L)), jnp.int32)
    plits = ref.pack_bitplane(lits)
    pinc = ref.pack_bitplane(inc)

    def timed(fn):
        return _time(lambda: jax.block_until_ready(fn()))

    import jax
    best = None
    if stage == "eval":
        cands = []
        for t in EVAL_TILES:
            cands.append((ops.PATH_PACKED, t, lambda t=t:
                          ops.packed_clause_eval_op(
                              plits, pinc, eval_mode=True, n_bits=L, **t)))
            cands.append((ops.PATH_PACKED_MXU, t, lambda t=t:
                          ops.packed_clause_mxu_op(
                              plits, pinc, eval_mode=True, n_bits=L, **t)))
        cands.append((ops.PATH_MXU, {}, lambda:
                      ops.clause_eval_op(lits, inc, eval_mode=True)))
    elif stage == "train":
        w = jnp.asarray(rng.integers(-4, 5, (H, R)), jnp.int32)
        lab = jnp.asarray(rng.integers(0, H, (B,)), jnp.int32)
        neg = (lab + 1) % H
        rl = jnp.asarray(rng.integers(0, 1 << 16, (B, R)), jnp.uint32)
        msk = jnp.ones((R,), jnp.int32)
        hm = jnp.ones((H,), jnp.int32)
        args = (w, lab, neg, rl, rl, msk, hm, 32, 0)
        cands = [
            (ops.PATH_PACKED, dict(TRAIN_TILES[0]), lambda:
             ops.packed_step_op(plits, pinc, *args, n_bits=L)),
            (ops.PATH_FUSED, dict(TRAIN_TILES[0]), lambda:
             ops.fused_step_op(lits, inc, *args)),
            (ops.PATH_MXU, dict(TRAIN_TILES[0]), lambda:
             ops.unfused_step_op(lits, inc, *args)),
        ]
    elif stage == "ta":
        ta = jnp.asarray(rng.integers(0, 256, (R, L)), jnp.int32)
        fb = jnp.asarray(rng.random((B, R)) < 0.25, jnp.int32)
        cl = jnp.asarray(rng.integers(0, 2, (B, R)), jnp.int32)
        lm = jnp.ones((L,), jnp.int32)
        cands = [
            (ops.TA_COMPACT, dict(TA_TILES[0]), lambda:
             ops.ta_update_compact_op(ta, lits, cl, fb, fb, lm, pinc,
                                      1, 1 << 13)),
            (ops.TA_DENSE, dict(TA_TILES[0]), lambda:
             ops.ta_update_op(ta, lits, cl, fb, fb, lm, 1, 1 << 13)),
        ]
    else:
        raise ValueError(f"unknown autotune stage {stage!r}; use {STAGES}")

    for path, tiles, thunk in cands:
        # a candidate the device refuses is an error, not a loss: it
        # would otherwise silently "never win" and hide a broken kernel
        s = timed(thunk)
        if best is None or s < best["us"] / 1e6:
            best = {"path": path, "tiles": dict(tiles), "us": s * 1e6,
                    "source": "measure"}
    return best
