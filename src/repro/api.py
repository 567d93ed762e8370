"""One engine, every TM — the unified ``compile → program → run`` front-end.

The paper's core claim (§IV, Fig 5–6) is that ONE synthesised datapath runs
*any* TM model via run-time reprogramming.  This module is the toolchain
that makes the claim usable (the MATADOR lesson, arXiv:2403.10538): a
single front-end that lowers heterogeneous TM workloads onto one fixed
engine.

    spec   = TMSpec.coalesced(features=784, classes=10, clauses=128)
    engine = api.compile(api.tile_for(spec))        # compiled ONCE
    prog   = engine.lower(spec, jax.random.PRNGKey(0))   # pure data
    ...                                             # engine.train_step(...)

or, batteries included, the uniform estimator shell:

    tm = api.TM(spec)
    tm.fit(x, y, epochs=3)
    tm.score(x_test, y_test)
    tm.save("ckpt/")                                # via repro.checkpoint

Five spec kinds lower onto the same engine executables:

* ``vanilla`` / ``coalesced`` — the paper's two algorithms (Eq 3 block
  weights vs dense learned weights) on the flat datapath.
* ``conv``       — patch extraction is host-side :meth:`TMSpec.to_bool`;
  per-patch clause eval + OR-over-patches ride the shared clause datapath
  (patch axis padded to the engine's ``max_patches`` and masked).
* ``regression`` — a program *flag*: error-driven clause selection through
  the same Alg-3 fixed-point margin compare, weights frozen.
* ``head``       — a CoTM whose thermometer booleanizer is folded into the
  spec (the lowered program sees ordinary literals).

Swapping programs (any kind → any kind) never recompiles an engine stage;
``engine.cache_report()`` proves it and ``launch/serve_tm.py`` serves it.

Session-centric execution (ISSUE 4): ``TM.fit`` stages its data once and
runs each epoch as a single device-resident scan
(``engine.bind(program, x, y)`` → :class:`repro.core.dtm.TMSession`),
bit-identical to the per-batch host loop it replaced; and :func:`stack`
builds a :class:`ProgramBank` — K same-tile programs vmapped through one
launch — for ensembles and program-major multi-tenant serving.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.core.booleanize import Booleanizer, fit_thermometer
from repro.core.dtm import DTMEngine, DTMProgram, TMSession
from repro.core.evaluate import accuracy, batched_predict
from repro.core.prng import PRNG
from repro.core.types import (COALESCED, PRNG_BACKENDS, TMConfig,
                              TileConfig, VANILLA)

KINDS = ("vanilla", "coalesced", "conv", "regression", "head")


@functools.lru_cache(maxsize=None)
def _position_code(img_h: int, img_w: int, patch: int) -> np.ndarray:
    """Thermometer patch-position bits [P, pos_bits] — a pure function of
    the conv geometry, built once per spec shape (not per batch).

    The cached array is SHARED across every caller with the same
    geometry, so it is returned read-only — an accidental in-place edit
    must fail loudly instead of silently corrupting all future encodes."""
    oh, ow = img_h - patch + 1, img_w - patch + 1
    pi = np.arange(oh)[:, None].repeat(ow, 1).reshape(-1)            # [P]
    pj = np.arange(ow)[None, :].repeat(oh, 0).reshape(-1)
    rt = (pi[:, None] > np.arange(oh - 1)[None, :]).astype(np.int8)
    ct = (pj[:, None] > np.arange(ow - 1)[None, :]).astype(np.int8)
    out = np.concatenate([rt, ct], -1)
    out.flags.writeable = False
    return out


@dataclasses.dataclass(frozen=True, eq=False)
class TMSpec:
    """Tagged union over the TM model family — everything ``lower`` needs.

    Use the per-kind constructors (``TMSpec.vanilla(...)`` etc.); the raw
    dataclass fields are the serialised form (``to_dict``/``from_dict``).
    """

    kind: str
    features: int = 0                 # flat kinds: Boolean feature count
    clauses: int = 128                # CoTM pool size / Vanilla per-class
    classes: int = 2
    T: int = 16
    s: float = 4.0
    ta_bits: int = 8
    weight_bits: int = 12
    rand_bits: int = 16
    prng_backend: str = "counter"
    lfsr_bits: int = 24               # PRNG lane width (lfsr backend)
    seed_refresh: bool = True         # master re-seeding every 2^L cycles
    boost_true_positive: bool = True
    # conv geometry (kind == "conv")
    img_h: int = 0
    img_w: int = 0
    patch: int = 0
    # head booleanizer (kind == "head"): thermometer cuts [f_raw, bits]
    thresholds: Optional[np.ndarray] = None

    # ---- constructors ------------------------------------------------------
    @classmethod
    def vanilla(cls, features: int, classes: int, clauses: int = 128,
                **kw) -> "TMSpec":
        return cls(kind="vanilla", features=features, classes=classes,
                   clauses=clauses, **kw)

    @classmethod
    def coalesced(cls, features: int, classes: int, clauses: int = 128,
                  **kw) -> "TMSpec":
        return cls(kind="coalesced", features=features, classes=classes,
                   clauses=clauses, **kw)

    @classmethod
    def conv(cls, img_h: int, img_w: int, patch: int, classes: int,
             clauses: int = 64, **kw) -> "TMSpec":
        assert 0 < patch <= min(img_h, img_w)
        return cls(kind="conv", img_h=img_h, img_w=img_w, patch=patch,
                   classes=classes, clauses=clauses, **kw)

    @classmethod
    def regression(cls, features: int, clauses: int = 128, T: int = 128,
                   s: float = 3.0, **kw) -> "TMSpec":
        return cls(kind="regression", features=features, clauses=clauses,
                   T=T, s=s, **kw)

    @classmethod
    def head(cls, calib: np.ndarray, classes: int, therm_bits: int = 4,
             clauses: int = 128, T: int = 64, s: float = 5.0,
             **kw) -> "TMSpec":
        """CoTM readout over float features; fits the thermometer
        booleanizer from a calibration array [n, f_raw]."""
        booleanizer = fit_thermometer(np.asarray(calib), bits=therm_bits)
        return cls(kind="head", classes=classes, clauses=clauses, T=T, s=s,
                   thresholds=booleanizer.thresholds, **kw)

    @classmethod
    def from_config(cls, cfg: TMConfig) -> "TMSpec":
        """The flat spec of a :class:`TMConfig` (e.g. the paper's models
        in ``configs/tm_paper.py``), every hyper-parameter carried over."""
        kind = "vanilla" if cfg.tm_type == VANILLA else "coalesced"
        return cls(kind=kind, features=cfg.features, clauses=cfg.clauses,
                   classes=cfg.classes, T=cfg.T, s=cfg.s,
                   ta_bits=cfg.ta_bits, weight_bits=cfg.weight_bits,
                   rand_bits=cfg.rand_bits, prng_backend=cfg.prng_backend,
                   lfsr_bits=cfg.lfsr_bits, seed_refresh=cfg.seed_refresh,
                   boost_true_positive=cfg.boost_true_positive)

    def __post_init__(self):
        assert self.kind in KINDS, self.kind
        if self.prng_backend not in PRNG_BACKENDS:
            raise ValueError(
                f"prng_backend={self.prng_backend!r} not recognised; "
                f"use one of {PRNG_BACKENDS}")

    # ---- derived geometry --------------------------------------------------
    @property
    def pos_bits(self) -> int:
        # thermometer-coded patch upper-left position (Granmo §3)
        return (self.img_h - self.patch) + (self.img_w - self.patch)

    @property
    def n_patches(self) -> int:
        if self.kind != "conv":
            return 1
        return (self.img_h - self.patch + 1) * (self.img_w - self.patch + 1)

    @property
    def bool_features(self) -> int:
        """Boolean features seen by the clause datapath."""
        if self.kind == "conv":
            return self.patch * self.patch + self.pos_bits
        if self.kind == "head":
            return int(self.thresholds.shape[0] * self.thresholds.shape[1])
        return self.features

    def tm_config(self) -> TMConfig:
        common = dict(features=self.bool_features, clauses=self.clauses,
                      s=self.s, ta_bits=self.ta_bits,
                      weight_bits=self.weight_bits, rand_bits=self.rand_bits,
                      prng_backend=self.prng_backend,
                      lfsr_bits=self.lfsr_bits,
                      seed_refresh=self.seed_refresh,
                      boost_true_positive=self.boost_true_positive)
        if self.kind == "vanilla":
            return TMConfig(tm_type=VANILLA, classes=self.classes, T=self.T,
                            **common)
        if self.kind == "regression":
            # classes=2 is the minimum legal geometry; the class machinery
            # is bypassed by the program's regression flag
            return TMConfig(tm_type=COALESCED, classes=2,
                            T=min(self.T, 8191), **common)
        return TMConfig(tm_type=COALESCED, classes=self.classes, T=self.T,
                        **common)

    # ---- host-side input encoding (engine.encode finishes the layout) ------
    @property
    def raw_is_bool(self) -> bool:
        """Raw input already is the Boolean features: :meth:`to_bool` is
        the identity (vanilla, coalesced, regression)."""
        return self.kind in ("vanilla", "coalesced", "regression")

    def to_bool(self, x: jax.Array) -> jax.Array:
        """Raw model input -> Boolean features.

        vanilla/coalesced/regression: [B, f] {0,1} passthrough;
        head: [B, f_raw] float -> thermometer bits [B, f_raw*k];
        conv: [B, H, W] {0,1} images -> patch features [B, P, f_patch]."""
        if self.raw_is_bool:
            return jnp.asarray(x)
        if self.kind == "head":
            return Booleanizer(self.thresholds)(jnp.asarray(x))
        return self._patch_features(jnp.asarray(x))

    def _patch_features(self, images: jax.Array) -> jax.Array:
        """[B, H, W] {0,1} -> [B, P, patch² + pos_bits] (bits + thermometer
        position code), the Granmo conv literal recipe minus the complement
        half (the engine layout adds it)."""
        B = images.shape[0]
        kh = kw = self.patch
        oh, ow = self.img_h - kh + 1, self.img_w - kw + 1
        rows = []
        for di in range(kh):            # static loops — K is tiny
            for dj in range(kw):
                rows.append(images[:, di:di + oh, dj:dj + ow])
        patches = jnp.stack(rows, axis=-1).reshape(B, oh * ow, kh * kw)
        pos = jnp.asarray(_position_code(self.img_h, self.img_w, self.patch))
        pos = jnp.broadcast_to(pos[None], (B, *pos.shape))
        return jnp.concatenate([patches.astype(jnp.int8), pos], -1)

    # ---- label/output codec (ONE definition for estimator AND server) ------
    def encode_labels(self, y) -> jax.Array:
        """Targets -> the int32 labels the engine step consumes.

        Regression: floats in [0, 1] -> integer vote targets in [0, T];
        everything else: class ids."""
        if self.kind == "regression":
            t = self.tm_config().T
            v = jnp.round(jnp.asarray(y, jnp.float32) * t)
            return jnp.clip(v, 0, t).astype(jnp.int32)
        return jnp.asarray(y, jnp.int32)

    def decode_output(self, sums: jax.Array, cl: jax.Array) -> jax.Array:
        """Engine infer outputs -> model prediction.

        Regression: clipped clause-vote count scaled back to [0, 1]
        float32; everything else: argmax class ids."""
        if self.kind == "regression":
            t = self.tm_config().T
            votes = jnp.clip(cl.sum(-1), 0, t)
            return votes.astype(jnp.float32) / t
        return jnp.argmax(sums, axis=-1)

    # ---- serialisation (repro.checkpoint extra payload) --------------------
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["thresholds"] is not None:
            d["thresholds"] = np.asarray(d["thresholds"]).tolist()
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TMSpec":
        d = dict(d)
        if d.get("thresholds") is not None:
            d["thresholds"] = np.asarray(d["thresholds"], np.float32)
        return cls(**d)


# ---------------------------------------------------------------------------
# compile — the "synthesis" step (once per engine geometry)
# ---------------------------------------------------------------------------

def tile_for(*specs: TMSpec, x: int = 128, y: int = 128, m: int = 128,
             n: int = 8, batch_tile: int = 8) -> TileConfig:
    """Smallest engine geometry that fits every given spec (multi-tenant
    sizing: pass all models a server will host)."""
    assert specs
    cfgs = [s.tm_config() for s in specs]
    return TileConfig(
        x=x, y=y, m=m, n=n, batch_tile=batch_tile,
        max_features=max(c.features for c in cfgs),
        max_clauses=max(c.total_clauses for c in cfgs),
        max_classes=max(c.classes for c in cfgs),
        max_patches=max(s.n_patches for s in specs))


@dataclasses.dataclass(frozen=True)
class PodPlan:
    """A per-mesh execution plan (the MATADOR per-deployment mapping,
    mesh edition) — what :func:`plan_for` decided and why.

    ``mode``: ``"single"`` (one device — no sharding), ``"tenants"``
    (programs fit the per-device budget: tenant-parallel
    :class:`repro.launch.pod.PodBank` over ``axis``), or ``"clauses"``
    (over-budget program: clause-shard one machine over ``axis`` with
    :class:`repro.launch.pod.ShardedTM`).
    """

    mode: str
    axis: str
    shards: int
    tile: TileConfig
    program_bytes: int
    budget_bytes: int
    reason: str


def plan_for(mesh, *specs: TMSpec, vmem_budget: Optional[float] = None,
             **tile_kw) -> PodPlan:
    """Grow :func:`tile_for` into a per-mesh planner: size the engine for
    the roster, then choose tenant- vs clause-sharding from the
    ``launch/tm_perf`` roofline model.

    A program whose padded RAM image (:func:`repro.launch.tm_perf
    .program_bytes`) fits the per-device budget (``vmem_budget``,
    default the hardware model's VMEM) serves tenant-parallel — D
    device-local banks, zero collectives.  An over-budget program
    clause-shards instead: the fewest shards (dividing the padded R,
    bounded by the mesh) that bring the per-shard window under budget,
    trading one ``[B, H]`` class-sum psum per step for fitting at all.
    """
    # lazy imports: api is the front-end layer; launch/ pulls it back in
    from repro.launch.mesh import hardware_model, mesh_chips
    from repro.launch import tm_perf

    tile = tile_for(*specs, **tile_kw)
    L, R, H = tile.padded_dims()
    ta_bits = max(s.ta_bits for s in specs)
    pbytes = tm_perf.program_bytes(L, R, H, ta_bits=ta_bits)
    budget = int(vmem_budget if vmem_budget is not None
                 else hardware_model(mesh.devices.flat[0]).vmem_bytes)
    n = mesh_chips(mesh)
    axes = mesh.axis_names
    if n <= 1:
        return PodPlan("single", axes[0] if axes else "", 1, tile, pbytes,
                       budget, "one device — nothing to shard")
    if pbytes <= budget:
        axis = "tenants" if "tenants" in axes else axes[0]
        return PodPlan(
            "tenants", axis, n, tile, pbytes, budget,
            f"program image {pbytes}B fits the {budget}B device budget: "
            f"tenant-parallel bank over '{axis}' ({n} devices)")
    axis = "clauses" if "clauses" in axes else axes[-1]
    shards = 1
    for s in range(2, n + 1):
        if R % s:
            continue
        shards = s
        if pbytes // s <= budget:
            break
    return PodPlan(
        "clauses", axis, shards, tile, pbytes, budget,
        f"program image {pbytes}B exceeds the {budget}B device budget: "
        f"clause-shard R={R} over '{axis}' x{shards} "
        f"({pbytes // shards}B per shard window)")


def compile(tile: Optional[TileConfig] = None, backend: str = "auto",
            rand_bits: int = 16) -> DTMEngine:
    """Compile the one engine (the FPGA 'synthesis' analogue).  Everything
    after this — any model, any TM kind — is programming, not compiling."""
    return DTMEngine(tile or TileConfig(), rand_bits=rand_bits,
                     backend=backend)


# ---------------------------------------------------------------------------
# TM — the uniform estimator shell (replaces the five bespoke drivers)
# ---------------------------------------------------------------------------

class TM:
    """``fit / partial_fit / predict / score / save / load`` for any TMSpec.

    Owns a :class:`DTMProgram` (+ PRNG stream) and runs it on a shared or
    private compiled-once :class:`DTMEngine`.  ``score`` returns accuracy
    for classification kinds and ``-MAE`` for regression (higher = better).
    """

    def __init__(self, spec: TMSpec, engine: Optional[DTMEngine] = None,
                 tile: Optional[TileConfig] = None, backend: str = "auto",
                 seed: int = 0):
        self.spec = spec
        self.cfg = spec.tm_config()
        self.engine = (engine if engine is not None
                       else compile(tile or tile_for(spec), backend,
                                    rand_bits=self.cfg.rand_bits))
        self.program: DTMProgram = self.engine.lower(
            spec, jax.random.PRNGKey(seed))
        self.prng = PRNG.create(self.cfg, seed + 1)
        self.steps = 0
        self._stream = None      # lazy streaming TMSession (partial_fit)
        # lifetime Alg-6 skip accounting (device-lazy accumulators — no
        # extra host sync on the training hot path; see ``skip_frac``)
        self._skip_active = 0
        self._skip_total = 0

    # ---- data plumbing -----------------------------------------------------
    def _encode(self, x) -> jax.Array:
        return self.engine.encode(self.spec, jnp.asarray(x))

    def _extra_metrics(self) -> Optional[Callable]:
        if self.spec.kind != "regression":
            return None
        # accuracy is not defined against vote targets — report MAE
        return lambda agg, n: {
            "train_mae": agg.get("abs_err", 0) / max(n * self.cfg.T, 1),
            "train_acc": None}

    # ---- training (both paths run through engine.bind sessions) ------------
    def partial_fit(self, x, y) -> dict:
        """One engine train step on a batch; returns the stats dict."""
        if self._stream is None:
            self._stream = self.engine.bind(self.program, spec=self.spec,
                                            prng=self.prng)
        # the estimator owns (program, prng); sync the streaming session
        # in case they were replaced from outside (load, surgery)
        self._stream.program, self._stream.prng = self.program, self.prng
        stats = self._stream.step(x, y)
        self.program, self.prng = self._stream.state()
        self.steps += 1
        self._skip_active = self._skip_active + stats["active_groups"]
        self._skip_total = self._skip_total + stats["total_groups"]
        return stats

    def fit(self, x, y, epochs: int = 1, batch: int = 32,
            log_every: int = 0, x_test=None, y_test=None,
            rng: Optional[np.random.Generator] = None) -> list:
        """Device-resident training: stage (x, y) once, then ONE scan
        launch per epoch (``engine.bind`` → ``TMSession.fit_epochs``) —
        bit-identical to the per-batch host loop it replaced."""
        session = self.engine.bind(self.program, x, y, spec=self.spec,
                                   prng=self.prng)

        def _score(xt, yt):
            # sync the estimator to the session's live program so score()
            # (and anything else reading self.program mid-fit) is current
            self.program, self.prng = session.state()
            return self.score(xt, yt)

        steps_before = session.steps
        try:
            history = session.fit_epochs(
                epochs, batch=batch, rng=rng, log_every=log_every,
                score_fn=(None if x_test is None else _score),
                x_test=x_test, y_test=y_test,
                extra_metrics=self._extra_metrics())
        finally:
            # epoch launches DONATE the program/PRNG buffers, so the
            # objects this estimator held going in are dead after the
            # first epoch — always take the session's live state back,
            # even when an epoch / score callback raises mid-fit
            self.program, self.prng = session.unbind()
            self.steps += session.steps - steps_before
        for rec in history:
            self._skip_active = self._skip_active + rec["active_groups"]
            self._skip_total = self._skip_total + rec["total_groups"]
        return history

    @property
    def skip_frac(self) -> Optional[float]:
        """Lifetime Alg-6 clause-skip fraction: share of y-wide clause
        groups whose TA tiles received NO feedback (and were therefore
        skipped by the compacted TA-update datapath) over all training
        this estimator has done.  ``None`` before any training."""
        tot = int(self._skip_total)
        if tot == 0:
            return None
        return 1.0 - int(self._skip_active) / tot

    # ---- inference ---------------------------------------------------------
    def _infer(self, x):
        lits = self._encode(x)
        return self.engine.infer_fn(self.spec)(self.program, lits)

    def predict(self, x) -> jax.Array:
        """Class ids [B] (classification) or predictions in [0,1] [B]
        (regression)."""
        return self.spec.decode_output(*self._infer(x))

    def class_sums(self, x) -> jax.Array:
        sums, _ = self._infer(x)
        return sums

    def score(self, x, y, batch: int = 256) -> float:
        if self.spec.kind == "regression":
            pred = batched_predict(self.predict, x, batch=batch)
            return -float(np.abs(pred - np.asarray(y)).mean())
        return accuracy(self.predict, x, y, batch=batch)

    # ---- persistence (repro.checkpoint: atomic, step-addressed) ------------
    def save(self, ckpt_dir: str, step: Optional[int] = None,
             keep: int = 3) -> str:
        tree = {"ta": self.program.ta, "weights": self.program.weights,
                "prng": self.prng}
        extra = {"spec": self.spec.to_dict(),
                 "tile": dataclasses.asdict(self.engine.tile),
                 "backend": self.engine.backend, "steps": self.steps}
        return checkpoint.save(ckpt_dir, self.steps if step is None else step,
                               tree, extra=extra, keep=keep)

    @classmethod
    def load(cls, ckpt_dir: str, engine: Optional[DTMEngine] = None,
             step: Optional[int] = None, seed: int = 0) -> "TM":
        step = checkpoint.latest_step(ckpt_dir) if step is None else step
        assert step is not None, f"no checkpoint under {ckpt_dir}"
        with open(os.path.join(ckpt_dir, f"step_{step:08d}",
                               "meta.json")) as f:
            extra = json.load(f)["extra"]
        spec = TMSpec.from_dict(extra["spec"])
        if engine is None:
            engine = compile(TileConfig(**extra["tile"]),
                             backend=extra["backend"],
                             rand_bits=spec.tm_config().rand_bits)
        tm = cls(spec, engine=engine, seed=seed)
        tree, _ = checkpoint.restore(
            ckpt_dir, step,
            like={"ta": tm.program.ta, "weights": tm.program.weights,
                  "prng": tm.prng})
        tm.program = dataclasses.replace(
            tm.program, ta=jnp.asarray(tree["ta"]),
            weights=jnp.asarray(tree["weights"]))
        # TA states were replaced wholesale — rebuild the packed include
        # bitplane the training stages otherwise maintain incrementally
        tm.program = engine.refresh_include(tm.program)
        tm.prng = tree["prng"]
        tm.steps = int(extra.get("steps", 0))
        return tm


# ---------------------------------------------------------------------------
# ProgramBank — K stacked programs, one launch (program-major serving)
# ---------------------------------------------------------------------------

class ProgramBank:
    """K same-tile :class:`DTMProgram` s stacked along a leading axis.

    The engine's stage executables are vmapped over the program axis
    (``infer_bank`` / ``train_bank``), so ensembles and multi-tenant
    serving execute K programs in ONE launch instead of K sequential
    program swaps.  The stacked pytree is plain data — per-slot hot-swap
    (``swap_in``/``swap_out``) is a device-side row scatter/gather, and
    ``unstack()`` recovers the K independent programs bit-exactly.

    Build with :func:`stack`; all programs must share the engine's tile
    geometry (they already do if lowered by it) and leaf dtypes (mixed
    ``ta_bits`` regimes would silently promote under ``jnp.stack``).
    Flat and conv programs cannot share a bank (literal ranks differ);
    ``conv=True`` routes through the conv bank executable.
    """

    def __init__(self, engine: DTMEngine, progs: DTMProgram, k: int,
                 conv: bool = False,
                 prngs: Optional[PRNG] = None):
        self.engine = engine
        self.progs = progs          # stacked leaves: [K, ...]
        self.k = k
        self.conv = conv
        self.prngs = prngs          # stacked PRNG (train-capable banks)

    # ---- one-launch execution ---------------------------------------------
    def infer(self, lits: jax.Array):
        """lits [K, B, W] packed ([K, B, P, W] conv) ->
        (sums [K, B, H], clause [K, B, R]) in one launch."""
        fn = (self.engine.infer_bank if not self.conv
              else self.engine.infer_conv_bank)
        return fn(self.progs, lits)

    def predict(self, lits):
        """Flat banks only: one launch with IN-TRACE decode ->
        (argmax preds [K, B] int32, clipped clause votes [K, B] int32) —
        the two tiny planes serving needs (classification reads preds,
        regression reads votes / T), instead of round-tripping the full
        sums/clause tensors to the host."""
        assert not self.conv, "conv banks decode host-side (use infer)"
        return self.engine.predict_bank(self.progs, lits)

    def predict_raw(self, feats: jax.Array, n_feats: jax.Array):
        """:meth:`predict` from raw Boolean features (K feature-major
        ``[L/2, B]`` int8 slots and their feature counts), encoded in the
        same launch — the serving path's one-dispatch cycle
        (``DTMEngine.predict_bank_raw``)."""
        assert not self.conv, "conv banks decode host-side (use infer)"
        return self.engine.predict_bank_raw(self.progs, feats, n_feats)

    def train(self, lits: jax.Array, labels: jax.Array) -> dict:
        """One stacked training step: program k consumes batch k
        (lits [K, B, W], labels [K, B]).  Returns per-program stats
        ([K]-shaped scalars); the bank's programs and PRNGs advance in
        place.  Conv banks are inference-only (the conv train stage's
        per-(datapoint, clause) patch gather is memory-hungry under vmap
        — train conv tenants through their own sessions)."""
        assert not self.conv, "conv banks are inference-only"
        assert self.prngs is not None, (
            "bank built without PRNGs; pass prngs= to api.stack")
        self.progs, self.prngs, stats = self.engine.train_bank(
            self.progs, self.prngs, lits, labels)
        return stats

    # ---- per-slot hot swap --------------------------------------------------
    def swap_in(self, k: int, program: DTMProgram) -> None:
        """Replace slot ``k`` (device-side row scatter per leaf) — the
        per-tenant RAM rewrite, bank edition."""
        self.progs = jax.tree.map(lambda b, p: b.at[k].set(p), self.progs,
                                  program)

    def swap_out(self, k: int) -> DTMProgram:
        """Read slot ``k`` back as an independent program."""
        return jax.tree.map(lambda b: b[k], self.progs)

    def unstack(self) -> List[DTMProgram]:
        return [self.swap_out(i) for i in range(self.k)]

    @property
    def nbytes(self) -> int:
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.progs))


def stack(programs: Sequence[DTMProgram], engine: DTMEngine,
          conv: bool = False,
          prngs: Optional[Sequence[PRNG]] = None) -> ProgramBank:
    """Stack same-tile programs into a :class:`ProgramBank`.

    ``prngs`` (optional, one per program) arms the bank for stacked
    training; their static config (backend, rand_bits, …) must agree —
    it becomes part of the single vmapped trace."""
    programs = list(programs)
    assert programs, "stack() needs at least one program"
    ref_leaves = jax.tree.leaves(programs[0])
    for p in programs[1:]:
        leaves = jax.tree.leaves(p)
        assert len(leaves) == len(ref_leaves)
        for a, b in zip(ref_leaves, leaves):
            assert a.shape == b.shape and a.dtype == b.dtype, (
                "bank programs must share padded shapes and dtypes "
                f"(got {a.shape}/{a.dtype} vs {b.shape}/{b.dtype}) — "
                "lower them on one engine with uniform ta_bits")
    progs = jax.tree.map(lambda *xs: jnp.stack(xs), *programs)
    stacked_prng = None
    if prngs is not None:
        prngs = list(prngs)
        assert len(prngs) == len(programs)
        stacked_prng = jax.tree.map(lambda *xs: jnp.stack(xs), *prngs)
    return ProgramBank(engine, progs, k=len(programs), conv=conv,
                       prngs=stacked_prng)


# ---------------------------------------------------------------------------
# serve — the full async serving stack in one call
# ---------------------------------------------------------------------------

def serve(roster: Optional[dict], batch_slot: int = 32,
          backend: str = "auto", mesh=None, config=None,
          slas: Optional[dict] = None, seed: int = 0,
          durable_dir: Optional[str] = None, ckpt_keep: int = 3,
          injector=None):
    """Build the async serving stack for a tenant roster in one call:
    a :func:`tile_for`-sized engine, a multi-tenant
    :class:`repro.launch.serve_tm.TMServer` (pod-sharded when ``mesh``
    spans > 1 device) and a
    :class:`repro.launch.scheduler.TMScheduler` in front of it.

    ``roster`` maps tenant name -> :class:`TMSpec`; ``slas`` (optional)
    maps tenant name -> :class:`repro.launch.scheduler.SLAClass`;
    ``config`` is a :class:`repro.launch.scheduler.SchedulerConfig`.
    Returns the scheduler (its ``.server`` / ``.server.engine`` expose
    the layers below).  Call ``.start()`` for the background flush loop
    or drive it inline with ``.step()`` / ``.drain()``.

    Durable streaming (ISSUE 10): with ``durable_dir`` set, tenant
    programs restore from their latest durable step (fresh tenants
    lower from their seed), each applied training step marks the tenant
    dirty for the async checkpoint writer, and the roster manifest is
    (re)written — so a crashed server cold-starts with
    ``api.serve(None, durable_dir=...)`` and continues bit-identically
    from the last durable step.  ``injector`` (a
    :class:`repro.runtime.fault.FaultInjector`) plumbs a deterministic
    failure schedule into the driver + writer boundaries (tests)."""
    # lazy imports: launch/ pulls this front-end module back in
    from repro.launch.scheduler import SLAClass, TMScheduler
    from repro.launch.serve_tm import TMServer
    from repro.runtime.durable import DurableStore, restore_tenant

    store = manifest = None
    seeds: dict = {}
    if durable_dir is not None:
        store = DurableStore(durable_dir, keep=ckpt_keep)
        manifest = store.read_manifest()
    if manifest is not None:
        seeds = {n: t["seed"] for n, t in manifest["tenants"].items()}
        if roster is None:             # cold-start: roster from manifest
            roster = {n: TMSpec.from_dict(t["spec"])
                      for n, t in manifest["tenants"].items()}
            batch_slot = manifest.get("batch_slot", batch_slot)
            if slas is None:
                slas = {n: SLAClass(**t["sla"])
                        for n, t in manifest["tenants"].items()
                        if t.get("sla") is not None}
    assert roster, ("serve() needs at least one tenant spec (or a "
                    "durable_dir with a manifest to cold-start from)")
    engine = compile(tile_for(*roster.values()), backend=backend)
    server = TMServer(engine, batch_slot=batch_slot, mesh=mesh)
    sched = TMScheduler(server, config=config, durable=store,
                        injector=injector)
    for i, (name, spec) in enumerate(roster.items()):
        tseed = seeds.setdefault(name, seed + i)
        sla = (slas or {}).get(name)
        restored = (restore_tenant(store, name, engine, spec, seed=tseed)
                    if store is not None else None)
        if restored is not None:
            program, prng, steps = restored
            sched.register(name, spec, program=program, prng=prng,
                           steps=steps, seed=tseed, sla=sla)
        else:
            sched.register(name, spec, seed=tseed, sla=sla)
    if store is not None:
        store.write_manifest({
            "version": 1, "batch_slot": batch_slot,
            "tenants": {
                n: {"spec": spec.to_dict(), "seed": seeds[n],
                    "sla": (None if (slas or {}).get(n) is None
                            else dataclasses.asdict((slas or {})[n]))}
                for n, spec in roster.items()}})
    return sched
