"""Public jit'd wrappers + batch-size–aware dispatch for the TM kernels.

Handles padding to tile multiples, backend dispatch (Pallas on TPU /
interpret-mode on CPU / pure-jnp reference), and the packed-path layout.
The DTM engine and benchmarks call these, never pl.pallas_call directly.

Two knobs are resolved HERE, once, for every kernel:

* ``REPRO_INTERPRET`` — ``auto`` (default: interpret iff the JAX backend is
  not a TPU), ``1`` (force interpret — CI determinism), ``0`` (force
  compiled).  Read at trace time; flip it before the first kernel call.
* ``REPRO_KERNEL_PATH`` — force one of
  ``mxu | packed_vpu | mxu_popcount | fused | ref`` instead of the
  shape-based :func:`select_path` choice.
* ``REPRO_SKIP`` — ``auto``/``1`` (default) runs the TA-update stage as the
  Alg-6 clause-skip compaction (:func:`ta_update_compact_op`, bit-identical
  to dense); ``0`` forces the dense update (the CI leg).  The decision is
  the SKIP dimension of the dispatch (:func:`select_ta_path`), recorded per
  train stage in ``cache_report()["path_per_stage"]``.
* ``REPRO_TA_PRNG`` — ``auto`` (default: the TA-update random stream is
  generated IN-KERNEL, family picked by the model's ``prng_backend``) or
  ``stream`` (materialise the identical stream as a [B, C, L] tensor and
  feed it to the kernel — the measured HBM-traffic baseline,
  benchmarks/fig15_lfsr.py).  ``inkernel`` is accepted as an explicit
  alias for auto's choice.
* ``REPRO_AUTOTUNE`` — ``off | seed | measure`` (kernels/autotune.py):
  when a workload SHAPE is handed to :func:`select_path` /
  :func:`select_ta_path`, the autotune plan for (device, stage, batch
  bucket, shape) is consulted before the heuristics below.

:func:`select_path` is the MATADOR-style datapath selector: the MXU matmul
recast for throughput batches, the bit-packed VPU path for the edge
single-datapoint regime, and the fused training-step kernel for train
steps (paper Fig 11 crossover; arXiv:2403.10538 §V).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from . import ref
from .class_sum import class_sum
from .clause_eval import clause_eval
from .fused_step import fused_step
from .packed_clause import packed_clause_eval, packed_clause_eval_mxu
from .ta_update import (ta_update, ta_update_conv, ta_update_sparse,
                        ta_update_streamed)

from .tm_infer import tm_infer

# Kernel path names (the dispatchable datapath variants).
PATH_MXU = "mxu"              # int8 matmul recast on the systolic array
PATH_PACKED = "packed_vpu"    # 32-literals-per-word bitwise VPU path
PATH_PACKED_MXU = "mxu_popcount"  # packed words -> int8 bitplane matmul
PATH_FUSED = "fused"          # single-launch training-step front half
PATH_REF = "ref"              # pure-jnp oracle (also the CPU fast path)
_PATHS = (PATH_MXU, PATH_PACKED, PATH_PACKED_MXU, PATH_FUSED, PATH_REF)

# TA-update random-stream provenance (the PRNG dimension of the dispatch).
TA_PRNG_INKERNEL = "inkernel"     # generate where you consume (default)
TA_PRNG_STREAM = "stream"         # [B, C, L] uint32 tensor from HBM
_TA_PRNGS = (TA_PRNG_INKERNEL, TA_PRNG_STREAM)

# Below this batch the matmul recast wastes systolic occupancy and the
# packed VPU path wins (edge single-datapoint regime, Fig 11).
PACKED_MAX_BATCH = 4

# TA-update execution modes (the SKIP dimension of the dispatch): the
# dense full-R update vs the Alg-6 clause-skip compaction that gathers
# only active clause groups (``ta_update_compact_op``).
TA_DENSE = "dense"
TA_COMPACT = "compact"

# Capacity buckets for the compacted TA update, as fractions of the clause
# group count.  Kept small and STATIC so the lax.switch over buckets traces
# once per jit entry (bounded cache); 1.0 (the dense fallback) is implicit.
# The 1/16 bucket is what a converged model actually rides (Fig 7:
# feedback falls to a few % of clauses) — without it the smallest-bucket
# floor caps the wall-clock saving long before convergence does.
SKIP_FRACTIONS = (0.0625, 0.25, 0.5)


def resolve_interpret() -> bool:
    """Single source of truth for Pallas interpret mode (REPRO_INTERPRET)."""
    env = os.environ.get("REPRO_INTERPRET", "auto").strip().lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    if env not in ("", "auto"):
        raise ValueError(
            f"REPRO_INTERPRET={env!r} not recognised; use auto, 1, or 0")
    return jax.default_backend() != "tpu"


def resolve_skip() -> bool:
    """Single source of truth for clause-skip execution (``REPRO_SKIP``).

    ``auto``/``1`` (default) — the TA-update stage runs the Alg-6
    compacted datapath (:func:`ta_update_compact_op`); ``0`` forces the
    dense update everywhere (the CI leg that keeps both modes green).
    Read at trace time, like ``REPRO_INTERPRET``."""
    env = os.environ.get("REPRO_SKIP", "auto").strip().lower()
    if env in ("1", "true", "yes", "on", "", "auto"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"REPRO_SKIP={env!r} not recognised; use auto, 1, or 0")


def resolve_ta_prng() -> str:
    """Single source of truth for the TA random-stream provenance
    (``REPRO_TA_PRNG``): :data:`TA_PRNG_INKERNEL` (default — zero HBM
    random-bits traffic) or :data:`TA_PRNG_STREAM` (the materialised
    baseline; bit-identical, B·C·L·4 extra bytes per step).  Read at
    trace time, like ``REPRO_INTERPRET``."""
    env = os.environ.get("REPRO_TA_PRNG", "auto").strip().lower()
    if env in ("", "auto", TA_PRNG_INKERNEL):
        return TA_PRNG_INKERNEL
    if env == TA_PRNG_STREAM:
        return TA_PRNG_STREAM
    raise ValueError(
        f"REPRO_TA_PRNG={env!r} not recognised; use auto, inkernel, or "
        "stream")


def select_ta_path(lanes: int = 1, shape=None) -> str:
    """The SKIP dimension of the dispatch: how the TA-update stage runs.

    Returns :data:`TA_COMPACT` (Alg-6 clause-skip compaction — gather the
    active clause groups, update only those, scatter back; bit-identical
    to dense) or :data:`TA_DENSE`.  Compaction is off under
    ``REPRO_SKIP=0`` and for vmapped program banks (``lanes`` > 1): vmap
    lowers the in-trace ``lax.switch`` over capacity buckets to a masked
    execution of EVERY branch per lane, which would cost more than dense.
    The engine records the decision per train stage in
    ``cache_report()["path_per_stage"]`` (key ``<stage>_ta``).

    ``shape`` (optional ``(L, R, H)``) additionally consults the autotune
    plan cache (kernels/autotune.py) — a MEASURED dense-vs-compact plan
    for this device/shape outranks the heuristic; no plan (or
    ``REPRO_AUTOTUNE=off``) falls through to it.  The streamed-rand
    baseline (``REPRO_TA_PRNG=stream``) has no compacted kernel, so it
    forces dense."""
    if lanes > 1 or not resolve_skip():
        return TA_DENSE
    if resolve_ta_prng() == TA_PRNG_STREAM:
        return TA_DENSE
    if shape is not None:
        from . import autotune
        planned = autotune.planned_path("ta", None, shape, lanes)
        if planned in (TA_DENSE, TA_COMPACT):
            return planned
    return TA_COMPACT


def resolve_kernel_path_force():
    """Single source of truth for the ``REPRO_KERNEL_PATH`` force:
    a validated path name, or None (heuristics / autotune decide).
    Typo'd forces raise instead of silently falling back (PR 8)."""
    env = os.environ.get("REPRO_KERNEL_PATH", "").strip().lower()
    if not env:
        return None
    if env not in _PATHS:
        raise ValueError(
            f"REPRO_KERNEL_PATH={env!r} not recognised; use one of {_PATHS}")
    return env


def select_path(cfg=None, batch=None, training: bool = False,
                lanes: int = 1, shape=None) -> str:
    """Pick the kernel path for a workload shape.

    cfg      optional TMConfig (reserved for model-shape heuristics)
    batch    datapoints per call PER PROGRAM (None = unknown ->
             throughput default)
    training True for the train-step datapath -> the fused kernel
    lanes    stacked-program width of the launch (ProgramBank vmap).
             The edge-regime test deliberately stays on the PER-PROGRAM
             batch: a vmapped bank lowers to a K-batched contraction —
             K independent [B, L] x [L, R] matmuls — so stacking does
             not improve per-instance MXU occupancy, and a bank of edge
             batches keeps the packed VPU path (32 literals per word,
             no per-program include unpack).  ``lanes`` is accepted so
             bank call sites hand the dispatcher the full launch
             geometry (recorded per stage; future tile-aware heuristics
             hook in here).
    shape    optional (L, R, H) workload geometry.  When given, the
             autotune plan cache (kernels/autotune.py; ``REPRO_AUTOTUNE``)
             is consulted FIRST — a measured or roofline-seeded plan for
             this (device, stage, batch bucket, shape) replaces the
             hand-tuned thresholds below.  ``None`` (or
             ``REPRO_AUTOTUNE=off``) keeps the heuristics.
    """
    env = resolve_kernel_path_force()
    if env is not None:
        return env
    if shape is not None:
        from . import autotune
        planned = autotune.planned_path("train" if training else "eval",
                                        batch, shape, lanes)
        if planned in _PATHS:
            return planned
    if batch is not None and batch <= PACKED_MAX_BATCH:
        # edge regime: the packed bitwise path wins for BOTH directions —
        # training's front half runs packed clause eval + the shared Alg-3
        # selection instead of the batch-parallel fused kernel (Fig 11).
        return PATH_PACKED
    if training:
        return PATH_FUSED
    return PATH_MXU


def _pad2(x: jax.Array, m0: int, m1: int, value=0) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)), constant_values=value)


def _pad1(x: jax.Array, m: int, value=0) -> jax.Array:
    p = (-x.shape[0]) % m
    return x if p == 0 else jnp.pad(x, (0, p), constant_values=value)


def lane_tile(n: int, t: int) -> int:
    """Block width along a lane (last) axis of extent ``n`` for a
    requested tile ``t``.  Mosaic takes a block that is a multiple of 128
    lanes or the whole axis, so a tile at least as wide as the axis
    becomes the whole axis; a narrower one must be 128-aligned."""
    if n <= t:
        return n
    if t % 128:
        raise ValueError(f"lane tile {t} is neither a multiple of 128 nor "
                         f"covers the whole {n}-wide axis")
    return t


@functools.partial(jax.jit, static_argnames=("eval_mode", "backend",
                                             "bt", "yt", "xt"))
def clause_eval_op(literals, include, eval_mode=False, backend="pallas",
                   bt=8, yt=128, xt=256):
    """[B,L]×[C,L] -> [B,C]; pads every dim, strips padding on return."""
    if backend == "ref":
        return ref.clause_eval_ref(literals, include, eval_mode)
    B, L = literals.shape
    C = include.shape[0]
    lit = _pad2(literals, bt, xt)
    inc = _pad2(include, yt, xt)
    out = clause_eval(lit, inc, eval_mode=eval_mode, bt=bt, yt=yt, xt=xt,
                      interpret=resolve_interpret())
    return out[:B, :C]


@functools.partial(jax.jit, static_argnames=("backend", "bt", "mt"))
def class_sum_op(clauses, weights, backend="pallas", bt=8, mt=128):
    if backend == "ref":
        return ref.class_sum_ref(clauses, weights)
    B, C = clauses.shape
    H = weights.shape[0]
    cl = _pad2(clauses, bt, mt)
    w = _pad2(weights, 8, mt)           # H padded to sublane multiple
    out = class_sum(cl, w, bt=bt, mt=mt, interpret=resolve_interpret())
    return out[:B, :H]


@functools.partial(jax.jit, static_argnames=("eval_mode", "backend",
                                             "bt", "yt", "xt"))
def tm_infer_op(literals, include, weights, eval_mode=True, backend="pallas",
                bt=8, yt=128, xt=256):
    """Fused inference [B,L]×[C,L]×[H,C] -> class sums [B,H]."""
    if backend == "ref":
        return ref.tm_infer_ref(literals, include, weights, eval_mode)
    B, L = literals.shape
    H = weights.shape[0]
    lit = _pad2(literals, bt, xt)
    inc = _pad2(include, yt, xt)
    w = _pad2(weights, 8, yt)
    out = tm_infer(lit, inc, w, eval_mode=eval_mode, bt=bt, yt=yt, xt=xt,
                   interpret=resolve_interpret())
    return out[:B, :H]


@functools.partial(jax.jit, static_argnames=("eval_mode", "backend",
                                             "n_bits", "bt", "yt", "wt"))
def packed_clause_eval_op(packed_literals, packed_include, eval_mode=False,
                          backend="pallas", n_bits=None, bt=8, yt=128,
                          wt=128):
    """Packed [B,W]×[C,W] -> [B,C].  ``n_bits`` (real literal count 2f)
    masks garbage tail bits past 2f in the last include word — zero include
    words never veto, so masking the include side neutralises ragged-W
    tails in both the firing and the eval-mode nonempty checks."""
    if backend == "ref":
        return ref.packed_clause_eval_ref(packed_literals, packed_include,
                                          eval_mode, n_bits=n_bits)
    if n_bits is not None:
        packed_include = ref.tail_mask_words(packed_include, n_bits)
    B, W = packed_literals.shape
    C = packed_include.shape[0]
    wt = lane_tile(W, wt)
    lit = _pad2(packed_literals, bt, wt)
    inc = _pad2(packed_include, yt, wt)
    out = packed_clause_eval(lit, inc, eval_mode=eval_mode, bt=bt, yt=yt,
                             wt=wt, interpret=resolve_interpret())
    return out[:B, :C]


@functools.partial(jax.jit, static_argnames=("eval_mode", "backend",
                                             "n_bits", "bt", "yt", "wt"))
def packed_clause_mxu_op(packed_literals, packed_include, eval_mode=False,
                         backend="pallas", n_bits=None, bt=8, yt=128,
                         wt=128):
    """Packed [B,W]×[C,W] -> [B,C] on the MXU popcount leg
    (:data:`PATH_PACKED_MXU`): uint32 words expand to int8 bitplanes
    in-register and clause violations become int8 dot products — same
    contract and bit-identical output as :func:`packed_clause_eval_op`,
    matmul-rate compute for throughput batches.  ``wt`` words are
    contracted per grid step (the whole row when W <= wt)."""
    if backend == "ref":
        return ref.packed_clause_mxu_ref(packed_literals, packed_include,
                                         eval_mode, n_bits=n_bits)
    if n_bits is not None:
        packed_include = ref.tail_mask_words(packed_include, n_bits)
    B, W = packed_literals.shape
    C = packed_include.shape[0]
    wt = lane_tile(W, wt)
    lit = _pad2(packed_literals, bt, wt)
    inc = _pad2(packed_include, yt, wt)
    out = packed_clause_eval_mxu(lit, inc, eval_mode=eval_mode, bt=bt,
                                 yt=yt, wt=wt,
                                 interpret=resolve_interpret())
    return out[:B, :C]


@functools.partial(jax.jit, static_argnames=(
    "rand_bits", "backend", "emit_include", "yt", "xt", "prng",
    "lfsr_bits", "seed_refresh", "stream"))
def ta_update_op(ta, literals, clause_out, type1, type2, l_mask, seed, p_ta,
                 rand_bits=16, boost=True, n_states=256, backend="pallas",
                 emit_include=False, yt=128, xt=256, row0=0,
                 prng="counter", lfsr_bits=24, seed_refresh=True,
                 stream=False):
    """Batched TA update [C,L] -> [C,L] (pads C/L, strips on return).

    ``seed``/``p_ta``/``boost``/``n_states``/``row0`` may be traced scalars
    — a new per-step seed or a DTMProgram swap never retraces.  ``ta`` may
    be any integer dtype (the engine stores int8-narrowed states, 4 per
    word); the returned states are int32 — callers narrow back.

    ``row0`` (default 0) offsets the PRNG stream keys' global row numbers:
    a clause shard holding rows [row0, row0 + C) of a larger machine
    updates them with exactly the streams a single-device launch would use
    for those rows (clause-sharded execution, launch/pod.py).

    ``prng``/``lfsr_bits``/``seed_refresh`` (static) select the random
    stream family — ``counter`` chains or the paper-faithful ``lfsr``
    cluster (kernels/ta_update.py docstring).  ``stream=True`` (static;
    normally driven by ``REPRO_TA_PRNG=stream`` via the engine) runs the
    measured baseline: the IDENTICAL stream is materialised as a
    [B, C, L] uint32 tensor (ref.ta_rand_stream at the padded keying) and
    consumed from HBM — bit-identical outputs, B·C·L·4 extra bytes.

    ``emit_include=True`` returns ``(new_ta, new_inc)`` where ``new_inc``
    is the packed include bitplane uint32 [C, ceil(L/32)] of the UPDATED
    states — the update stage maintains the engine's canonical bitplane
    incrementally, fused into this same jitted call, so no consumer ever
    re-thresholds the full [C, L] TA matrix afterwards."""
    C = ta.shape[0]
    B = literals.shape[0]
    if backend == "ref":
        rows = (jnp.asarray(row0, jnp.int32)
                + jnp.arange(C, dtype=jnp.int32))
        rands = None
        if stream:
            L = ta.shape[1]
            rands = ref.ta_rand_stream(seed, B, C, L, rand_bits, prng,
                                       lfsr_bits, seed_refresh, xt=xt,
                                       row_idx=rows)
        new_ta = ref.ta_update_ref(ta, literals, clause_out, type1, type2,
                                   l_mask, seed, p_ta, rand_bits, boost,
                                   n_states, row_idx=rows, prng=prng,
                                   lfsr_bits=lfsr_bits,
                                   seed_refresh=seed_refresh, rands=rands)
    else:
        C, L = ta.shape
        # The PRNG stream is keyed on the padded row stride (ceil(L/xt)*xt);
        # ref.ta_update_ref keys identically, so kernel and ref match
        # bit-for-bit on any shape.
        ta_p = _pad2(ta, yt, xt)
        lit_p = _pad2(literals, 1, xt)
        cl_p = _pad2(clause_out, 1, yt)
        t1_p = _pad2(type1, 1, yt)
        t2_p = _pad2(type2, 1, yt)
        lm = jnp.pad(l_mask, (0, (-L) % xt))
        if stream:
            # baseline: generate the SAME stream at the padded geometry
            # (keys row0 + padded row index) and ship it through HBM.
            C_pad, L_pad = ta_p.shape
            rows_p = (jnp.asarray(row0, jnp.uint32)
                      + jnp.arange(C_pad, dtype=jnp.uint32))
            rands = ref.ta_rand_stream(seed, B, C_pad, L_pad, rand_bits,
                                       prng, lfsr_bits, seed_refresh,
                                       xt=xt, row_idx=rows_p)
            out = ta_update_streamed(ta_p, lit_p, cl_p, t1_p, t2_p, lm,
                                     rands, p_ta=p_ta, boost=boost,
                                     n_states=n_states, yt=yt, xt=xt,
                                     interpret=resolve_interpret())
        else:
            out = ta_update(ta_p, lit_p, cl_p, t1_p, t2_p, lm, seed=seed,
                            p_ta=p_ta, rand_bits=rand_bits, boost=boost,
                            n_states=n_states, yt=yt, xt=xt, row0=row0,
                            prng=prng, lfsr_bits=lfsr_bits,
                            seed_refresh=seed_refresh,
                            interpret=resolve_interpret())
        new_ta = out[:C, :L]
    if emit_include:
        return new_ta, ref.pack_include(new_ta, n_states)
    return new_ta


@functools.partial(jax.jit, static_argnames=("rand_bits", "backend", "yt",
                                             "xt", "prng", "lfsr_bits",
                                             "seed_refresh"))
def ta_update_conv_op(ta, lit_words, clause_out, type1, type2, l_mask, seed,
                      p_ta, rand_bits=16, boost=True, n_states=256,
                      backend="pallas", yt=128, xt=256, row0=0,
                      prng="counter", lfsr_bits=24, seed_refresh=True):
    """Conv-TM TA update [C, L] -> ``(new_ta int32 [C, L], new_inc uint32
    [C, W])``: every clause row of datapoint ``b`` is updated against its
    own chosen patch, whose packed literals ``lit_words`` [B, C, W] carry
    (W = ceil(L/32)); ``clause_out``/``type1``/``type2`` are [2B, C] over
    both feedback rounds (target rounds first).  Pads C/L to whole tiles
    (W to the padded width) and strips on return, like
    :func:`ta_update_op`, whose streams (``seed``, ``row0``, ``prng``,
    ``lfsr_bits``, ``seed_refresh``, padded row stride) it shares: the
    Pallas kernel ``ta_update_conv`` generates the randoms in place, the
    ``ref`` backend runs its oracle.  Always dense; the packed include
    bitplane of the updated states is emitted in the same call."""
    C, L = ta.shape
    if backend == "ref":
        rows = (jnp.asarray(row0, jnp.int32)
                + jnp.arange(C, dtype=jnp.int32))
        new_ta = ref.ta_update_conv_ref(
            ta, lit_words, clause_out, type1, type2, l_mask, seed, p_ta,
            rand_bits, boost, n_states, xt=xt, row_idx=rows, prng=prng,
            lfsr_bits=lfsr_bits, seed_refresh=seed_refresh)
    else:
        ta_p = _pad2(ta, yt, xt)
        C_pad, L_pad = ta_p.shape
        words = jnp.pad(lit_words, ((0, 0), (0, C_pad - C),
                                    (0, L_pad // 32 - lit_words.shape[2])))
        out = ta_update_conv(
            ta_p, words, _pad2(clause_out, 1, yt), _pad2(type1, 1, yt),
            _pad2(type2, 1, yt), jnp.pad(l_mask, (0, L_pad - L)), seed=seed,
            p_ta=p_ta, rand_bits=rand_bits, boost=boost, n_states=n_states,
            yt=yt, xt=xt, row0=row0, prng=prng, lfsr_bits=lfsr_bits,
            seed_refresh=seed_refresh, interpret=resolve_interpret())
        new_ta = out[:C, :L]
    return new_ta, ref.pack_include(new_ta, n_states)


def _skip_caps(n_groups: int) -> tuple:
    """Static compaction capacity buckets (in clause groups) for a grid of
    ``n_groups`` — the unique ``ceil(n_groups * f)`` for
    :data:`SKIP_FRACTIONS`, strictly below the dense fallback."""
    caps = sorted({max(1, math.ceil(n_groups * f)) for f in SKIP_FRACTIONS})
    return tuple(c for c in caps if c < n_groups)


@functools.partial(jax.jit, static_argnames=("rand_bits", "backend",
                                             "group", "yt", "xt", "prng",
                                             "lfsr_bits", "seed_refresh"))
def ta_update_compact_op(ta, literals, clause_out, type1, type2, l_mask,
                         inc, seed, p_ta, rand_bits=16, boost=True,
                         n_states=256, backend="pallas", group=32,
                         yt=128, xt=256, row0=0, prng="counter",
                         lfsr_bits=24, seed_refresh=True):
    """Clause-skip TA update (Alg 6 made real): bit-identical to
    ``ta_update_op(..., emit_include=True)`` but touches only ACTIVE
    clause groups.

    A clause row is active iff any batch element gives it Type I or
    Type II feedback (``type1 | type2``); rows without feedback have a
    provably zero delta, so their TA tiles (and include-bitplane rows)
    need never move.  The active-group bitmap is compacted into a
    fixed-capacity index vector (``jnp.nonzero(size=k)`` — the prefix-sum
    compaction) at one of the static :data:`SKIP_FRACTIONS` capacity
    buckets, selected IN-TRACE by ``lax.switch`` with the dense kernel as
    the full-capacity fallback — jit caches stay bounded (one trace, all
    buckets) and a converged model takes the small-bucket branch at run
    time.  Kernel backend: the sparse scalar-prefetch kernel
    (:func:`repro.kernels.ta_update.ta_update_sparse`) gathers active
    (yt, xt) tiles; ref backend: ``jnp.take`` row gathers at ``group``-row
    granularity feeding the stream-exact oracle.

    ``inc`` must be the packed include bitplane OF ``ta`` (the engine's
    maintained invariant): skipped rows keep their bitplane words, updated
    rows are re-packed from the compacted output and scattered back.
    ``row0`` (traced scalar, default 0) offsets every stream key's global
    row number — a clause shard passes its first global row so its
    compacted update reproduces the matching rows of a single-device
    launch bit-for-bit (launch/pod.py).  ``prng``/``lfsr_bits``/
    ``seed_refresh`` (static) select the in-kernel stream family exactly
    as in :func:`ta_update_op` — compaction is stream-transparent for
    both families (keys ride the ORIGINAL row numbers).
    Returns ``(new_ta int32 [C, L], new_inc uint32 [C, W])``."""
    C, L = ta.shape
    g = yt if backend != "ref" else group
    n_groups = -(-C // g)
    C_pad = n_groups * g
    n_states_i = jnp.asarray(n_states, jnp.int32)

    row_act = ((type1 > 0) | (type2 > 0)).any(axis=0)              # [C]
    grp_act = jnp.pad(row_act, (0, C_pad - C)).reshape(n_groups, g).any(-1)
    n_act = grp_act.sum()
    caps = _skip_caps(n_groups)

    if backend == "ref":
        ta_p = jnp.pad(ta.astype(jnp.int32), ((0, C_pad - C), (0, 0)))
        cl_p = jnp.pad(clause_out, ((0, 0), (0, C_pad - C)))
        t1_p = jnp.pad(type1, ((0, 0), (0, C_pad - C)))
        t2_p = jnp.pad(type2, ((0, 0), (0, C_pad - C)))
        lit_p, lm = literals, l_mask
    else:
        ta_p = _pad2(ta.astype(jnp.int32), g, xt)
        cl_p = _pad2(clause_out, 1, g)
        t1_p = _pad2(type1, 1, g)
        t2_p = _pad2(type2, 1, g)
        lit_p = _pad2(literals, 1, xt)
        lm = jnp.pad(l_mask, (0, (-L) % xt))
    base = jnp.clip(ta_p, 0, n_states_i - 1)
    inc_p = jnp.pad(inc, ((0, C_pad - C), (0, 0)))

    def _compact_branch(k: int):
        def branch():
            gidx = jnp.nonzero(grp_act, size=k,
                               fill_value=n_groups - 1)[0].astype(jnp.int32)
            rows = (gidx[:, None] * g
                    + jnp.arange(g, dtype=jnp.int32)).reshape(-1)   # [k*g]
            if backend == "ref":
                upd = ref.ta_update_ref(
                    jnp.take(ta_p, rows, axis=0), lit_p,
                    jnp.take(cl_p, rows, axis=1),
                    jnp.take(t1_p, rows, axis=1),
                    jnp.take(t2_p, rows, axis=1), lm, seed, p_ta,
                    rand_bits, boost, n_states, xt=xt,
                    row_idx=rows + jnp.asarray(row0, jnp.int32),
                    prng=prng, lfsr_bits=lfsr_bits,
                    seed_refresh=seed_refresh)
            else:
                upd = ta_update_sparse(
                    ta_p, lit_p, cl_p, t1_p, t2_p, lm, gidx, seed=seed,
                    p_ta=p_ta, rand_bits=rand_bits, boost=boost,
                    n_states=n_states, yt=g, xt=xt, row0=row0,
                    prng=prng, lfsr_bits=lfsr_bits,
                    seed_refresh=seed_refresh,
                    interpret=resolve_interpret())
            # fill slots gather the last group (clamped, duplicate-safe:
            # they recompute identical values); scatter restores rows
            new_ta = base.at[rows].set(upd)
            new_inc = inc_p.at[rows].set(
                ref.pack_include(upd[:, :L], n_states))
            return new_ta, new_inc
        return branch

    def _dense_branch():
        if backend == "ref":
            new_ta = ref.ta_update_ref(
                ta_p, lit_p, cl_p, t1_p, t2_p, lm, seed, p_ta, rand_bits,
                boost, n_states, xt=xt,
                row_idx=(jnp.asarray(row0, jnp.int32)
                         + jnp.arange(C_pad, dtype=jnp.int32)),
                prng=prng, lfsr_bits=lfsr_bits, seed_refresh=seed_refresh)
        else:
            new_ta = ta_update(ta_p, lit_p, cl_p, t1_p, t2_p, lm, seed=seed,
                               p_ta=p_ta, rand_bits=rand_bits, boost=boost,
                               n_states=n_states, yt=g, xt=xt, row0=row0,
                               prng=prng, lfsr_bits=lfsr_bits,
                               seed_refresh=seed_refresh,
                               interpret=resolve_interpret())
        return new_ta, ref.pack_include(new_ta[:, :L], n_states)

    if caps:
        bidx = sum((n_act > jnp.int32(c)).astype(jnp.int32) for c in caps)
        new_ta, new_inc = jax.lax.switch(
            bidx, [_compact_branch(k) for k in caps] + [_dense_branch])
    else:       # a single clause group: nothing to compact
        new_ta, new_inc = _dense_branch()
    return new_ta[:C, :L], new_inc[:C]


@functools.partial(jax.jit, static_argnames=("rand_bits", "backend",
                                             "bt", "yt", "xt"))
def fused_step_op(literals, include, weights, labels, neg_labels,
                  rand_lab, rand_neg, cl_mask, h_mask, T, w_frozen,
                  rand_bits=16, backend="pallas", bt=8, yt=128, xt=256):
    """Fused training-step front half (clause eval + class sums + Alg-3
    feedback selection for both rounds) in ONE kernel launch.

    literals [B,L] {0,1}; include [R,L] {0,1}; weights [H,R] int32;
    labels/neg_labels [B] int32; rand_lab/rand_neg [B,R] uint32
    (< 2^rand_bits); cl_mask [R]; h_mask [H]; T / w_frozen int32 scalars
    (traced).  Pads every dim, strips padding on return.

    Returns (clause [B,R], class_sums [B,H] with Fig-6d pinning,
    sel_lab [B,R], sel_neg [B,R]) — all int32, bit-exact vs. the unfused
    ``clause_eval_op -> class_sum_op -> feedback-select`` pipeline and
    :func:`ref.fused_step_ref`.
    """
    if backend == "ref":
        return ref.fused_step_ref(literals, include, weights, labels,
                                  neg_labels, rand_lab, rand_neg, cl_mask,
                                  h_mask, T, w_frozen, rand_bits)
    B, L = literals.shape
    R = include.shape[0]
    H = weights.shape[0]
    # one-hots feed the in-kernel csum extraction; weight rows are plain
    # gathers (cheaper than the equivalent one-hot matmul, same values)
    hr = jnp.arange(H, dtype=jnp.int32)
    lab_oh = (labels[:, None] == hr[None, :]).astype(jnp.int32)    # [B, H]
    neg_oh = (neg_labels[:, None] == hr[None, :]).astype(jnp.int32)
    w_lab = jnp.take(weights, labels, axis=0)                      # [B, R]
    w_neg = jnp.take(weights, neg_labels, axis=0)

    lit = _pad2(literals, bt, xt)
    inc = _pad2(include, yt, xt)
    w = _pad2(weights, 8, yt)
    clause, sums, sel_lab, sel_neg = fused_step(
        lit, inc, w, _pad2(lab_oh, bt, 8), _pad2(neg_oh, bt, 8),
        _pad2(w_lab, bt, yt), _pad2(w_neg, bt, yt),
        _pad2(rand_lab, bt, yt), _pad2(rand_neg, bt, yt),
        _pad1(cl_mask.astype(jnp.int32), yt),
        _pad1(h_mask.astype(jnp.int32), 8),
        T, w_frozen, rand_bits=rand_bits, bt=bt, yt=yt, xt=xt,
        interpret=resolve_interpret())
    return (clause[:B, :R], sums[:B, :H], sel_lab[:B, :R], sel_neg[:B, :R])


@functools.partial(jax.jit, static_argnames=("rand_bits", "backend",
                                             "n_bits", "bt", "yt", "wt",
                                             "mxu"))
def packed_step_op(packed_literals, packed_include, weights, labels,
                   neg_labels, rand_lab, rand_neg, cl_mask, h_mask, T,
                   w_frozen, rand_bits=16, backend="pallas", n_bits=None,
                   bt=8, yt=128, wt=128, mxu=False):
    """Training-step front half on the bit-packed layout (edge batches).

    Same signature/outputs as :func:`fused_step_op`, but literals/include
    arrive as packed uint32 bitplanes ([B,W] / [R,W], W = ceil(2f/32)) —
    the engine's canonical on-device layout.  Clause eval runs the packed
    VPU kernel (32 literals per word, no MXU), or — ``mxu=True``, the
    :data:`PATH_PACKED_MXU` training leg — the bit-identical popcount-as-
    matmul kernel; class sums and the Alg-3 selection reuse the shared
    stages.  Bit-exact vs. ``fused_step_op`` on the corresponding dense
    inputs and vs. :func:`ref.packed_step_ref`.
    """
    if backend == "ref":
        return ref.packed_step_ref(packed_literals, packed_include, weights,
                                   labels, neg_labels, rand_lab, rand_neg,
                                   cl_mask, h_mask, T, w_frozen, rand_bits,
                                   n_bits=n_bits, mxu=mxu)
    if mxu:
        cl = packed_clause_mxu_op(packed_literals, packed_include,
                                  eval_mode=False, n_bits=n_bits, bt=bt,
                                  yt=yt, wt=wt)
    else:
        cl = packed_clause_eval_op(packed_literals, packed_include,
                                   eval_mode=False, n_bits=n_bits, bt=bt,
                                   yt=yt, wt=wt)
    cl = cl * cl_mask[None, :].astype(jnp.int32)
    sums = class_sum_op(cl, weights)
    sums = jnp.where(h_mask[None, :] > 0, sums, ref.NEG_INF_SUM)
    sel_lab = ref._round_select(sums, labels, 1, rand_lab, weights, cl_mask,
                                T, w_frozen, rand_bits)
    sel_neg = ref._round_select(sums, neg_labels, 0, rand_neg, weights,
                                cl_mask, T, w_frozen, rand_bits)
    return cl, sums, sel_lab, sel_neg


def round_select_op(sums, cls, y_c, rand, weights, cl_mask, T, w_frozen,
                    rand_bits=16):
    """Alg-3 integer-exact clause selection for one feedback round
    (public wrapper over the shared jnp formulation — identical on every
    backend, used by the engine's conv training stage and the unfused
    baseline)."""
    return ref._round_select(sums, cls, y_c, rand, weights, cl_mask, T,
                             w_frozen, rand_bits)


@functools.partial(jax.jit, static_argnames=("rand_bits",))
def unfused_step_op(literals, include, weights, labels, neg_labels,
                    rand_lab, rand_neg, cl_mask, h_mask, T, w_frozen,
                    rand_bits=16):
    """The seed three-stage pipeline, kept as the fused kernel's measured
    baseline: clause_eval launch -> HBM clause matrix -> class_sum launch ->
    jnp Alg-3 selection pass.  Same signature/outputs as fused_step_op."""
    cl = clause_eval_op(literals, include, eval_mode=False)
    cl = cl * cl_mask[None, :].astype(jnp.int32)
    sums = class_sum_op(cl, weights)
    sums = jnp.where(h_mask[None, :] > 0, sums, ref.NEG_INF_SUM)
    sel_lab = ref._round_select(sums, labels, 1, rand_lab, weights,
                                cl_mask, T, w_frozen, rand_bits)
    sel_neg = ref._round_select(sums, neg_labels, 0, rand_neg, weights,
                                cl_mask, T, w_frozen, rand_bits)
    return cl, sums, sel_lab, sel_neg
