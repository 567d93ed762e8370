"""Pallas TPU kernel: FUSED DTM training-step front half (paper Fig 9b/9c).

The FPGA keeps the whole train loop — clause evaluation, class sums,
feedback selection — inside one package with no off-chip round trips.  The
seed TPU port launched ``clause_eval`` and ``class_sum`` as separate Pallas
calls with an HBM materialisation of the ``[B, C]`` clause matrix between
them, and computed the feedback-selection comparison in plain jnp on a third
pass over the data.  This kernel fuses all three per grid step:

  for b-tile:                           (grid dim 0, parallel)
    for c-tile:                         (grid dim 1, sequential)
      for k-tile:                       (grid dim 2, literal slices)
        viol += (1-lit)ᵀ·inc            (MXU)
      clause_tile = (viol == 0)·clmask  (VPU, training-mode semantics)
      csum  += clause_tile · wᵀ         (MXU — clause tile consumed in VMEM)
    sums = mask(csum)                   (Fig 6d remainder pinning)
    sel[r] = rand·2T < (T ∓ clip(csum_r)) · 2^rand_bits   (Alg 3, both
                                         feedback rounds, integer-exact)

The clause matrix is written to HBM exactly once (the TA-update kernel
consumes it); the class-sum matmul reads it from VMEM scratch, and the
per-clause feedback-selection masks for the target and negated rounds are
emitted by the same launch — no separate kernel, no re-read.

The ``sel_lab``/``sel_neg`` masks this kernel emits are ALSO where the
clause-skip execution (Alg 6, ISSUE 5) is born: the engine derives the
Type I/II feedback masks from them, and the active-clause-group bitmap of
those masks drives the COMPACTED TA-update back half
(``ops.ta_update_compact_op`` → the scalar-prefetch gather kernel in
ta_update.py) — clause tiles this launch selects no feedback for never
move again for the rest of the step.

Dynamic (traced) scalars ride in SMEM so a :class:`DTMProgram` swap never
retraces: ``T`` and ``w_frozen`` are run-time model data (cache-size == 1
reconfiguration semantics, paper §IV-D-a).

Bit-exactness: every output equals the unfused
``clause_eval → class_sum → feedback-select`` pipeline and the
:mod:`repro.kernels.ref` oracle — int32 class sums, identical selection
masks (tests/test_fused_step.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .class_sum import N_LIMBS, limb_dot, weight_limbs
from .tpu_params import block_bytes, compiler_params
# Fig 6d: remainder class sums pinned to the datapath minimum — single
# definition shared with the oracles and the engine.
from .ref import NEG_INF_SUM


def _kernel(neg_lit_ref, inc_ref, w_tile_ref, lab_oh_ref, neg_oh_ref,
            w_lab_ref, w_neg_ref, rand_lab_ref, rand_neg_ref,
            clm_tile_ref, clm_full_ref, h_mask_ref, params_ref,
            clause_ref, sums_ref, sel_lab_ref, sel_neg_ref,
            viol_ref, acc_ref, *, n_c: int, n_k: int, rand_bits: int):
    c, k = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(c == 0, k == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k == 0)
    def _init_viol():
        viol_ref[...] = jnp.zeros_like(viol_ref)

    # int8 operands, int32 accumulation on the MXU
    viol_ref[...] += jax.lax.dot_general(
        neg_lit_ref[...], inc_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                 # [bt, yt]

    @pl.when(k == n_k - 1)
    def _consume_clause_tile():
        # training-mode semantics: empty clauses fire; padded rows are
        # zeroed by cl_mask (Fig 6b) — identical to DTMEngine._train_impl.
        fired = (viol_ref[...] == 0).astype(jnp.int32)
        clause = fired * clm_tile_ref[...]                # [bt, yt]
        clause_ref[...] = clause                          # single HBM write
        acc_ref[...] += limb_dot(clause.astype(jnp.int8),
                                 w_tile_ref)              # [bt, H]

        @pl.when(c == n_c - 1)
        def _select():
            T = params_ref[0, 0]
            w_frozen = params_ref[0, 1]
            sums = jnp.where(h_mask_ref[...] > 0, acc_ref[...],
                             NEG_INF_SUM)                 # [bt, H]
            sums_ref[...] = sums
            clm = clm_full_ref[...] > 0                   # [1, R]
            # two feedback rounds: (target, y_c=1) and (negated, y_c=0)
            for oh_ref, w_r_ref, rnd_ref, out_ref, y_c in (
                    (lab_oh_ref, w_lab_ref, rand_lab_ref, sel_lab_ref, 1),
                    (neg_oh_ref, w_neg_ref, rand_neg_ref, sel_neg_ref, 0)):
                oh = oh_ref[...]                          # [bt, H] one-hot
                csum = jnp.sum(oh * sums, axis=1, keepdims=True)
                cs = jnp.clip(csum, -T, T)                # [bt, 1]
                p_num = (T - cs) if y_c == 1 else (T + cs)
                w_r = w_r_ref[...]                        # [bt, R]
                lhs = rnd_ref[...].astype(jnp.int32) * (2 * T)
                sel = lhs < (p_num << rand_bits)
                # Vanilla eligibility: only the class's own block (w != 0).
                elig = jnp.logical_or(w_r != 0, w_frozen <= 0)
                out_ref[...] = (sel & clm & elig).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("rand_bits", "bt", "yt", "xt",
                                             "interpret"))
def fused_step(literals: jax.Array, include: jax.Array, weights: jax.Array,
               lab_oh: jax.Array, neg_oh: jax.Array,
               w_lab: jax.Array, w_neg: jax.Array,
               rand_lab: jax.Array, rand_neg: jax.Array,
               cl_mask: jax.Array, h_mask: jax.Array,
               T: jax.Array, w_frozen: jax.Array,
               rand_bits: int = 16, bt: int = 8, yt: int = 128,
               xt: int = 256, interpret: bool | None = None):
    """Fused training-step front half on tile-exact shapes (callers pad).

    literals [B, L] {0,1}; include [R, L] {0,1}; weights [H, R] int32;
    lab_oh/neg_oh [B, H] one-hot int32; w_lab/w_neg [B, R] int32 (weight row
    of each datapoint's target/negated class); rand_lab/rand_neg [B, R]
    uint32 (< 2^rand_bits); cl_mask [1, R]; h_mask [1, H]; T/w_frozen int32
    scalars (traced — a model swap never retraces).

    Returns (clause [B, R], class_sums [B, H], sel_lab [B, R],
    sel_neg [B, R]) — all int32, bit-exact vs. the unfused pipeline.
    ``interpret=None`` resolves through ``ops.resolve_interpret()``
    (DTM008).
    """
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, L = literals.shape
    R, L2 = include.shape
    H, R2 = weights.shape
    assert L == L2 and R == R2
    assert B % bt == 0 and R % yt == 0 and L % xt == 0, ((B, R, L, H),
                                                         (bt, yt, xt))
    neg_lit = (1 - literals).astype(jnp.int8)
    params = jnp.stack([jnp.asarray(T, jnp.int32),
                        jnp.asarray(w_frozen, jnp.int32)]).reshape(1, 2)
    grid = (B // bt, R // yt, L // xt)
    need = (block_bytes(((bt, xt), 1), ((yt, xt), 1), ((N_LIMBS, H, yt), 1),
                        ((bt, H), 4), ((bt, H), 4), ((bt, R), 4),
                        ((bt, R), 4), ((bt, R), 4), ((bt, R), 4),
                        ((1, yt), 4), ((1, R), 4), ((1, H), 4),
                        ((bt, yt), 4), ((bt, H), 4), ((bt, R), 4),
                        ((bt, R), 4))
            + (bt * yt + bt * H) * 4)
    return pl.pallas_call(
        functools.partial(_kernel, n_c=grid[1], n_k=grid[2],
                          rand_bits=rand_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, xt), lambda b, c, k: (b, k)),    # neg literals
            pl.BlockSpec((yt, xt), lambda b, c, k: (c, k)),    # include
            pl.BlockSpec((N_LIMBS, H, yt),
                         lambda b, c, k: (0, 0, c)),           # weight limbs
            pl.BlockSpec((bt, H), lambda b, c, k: (b, 0)),     # label one-hot
            pl.BlockSpec((bt, H), lambda b, c, k: (b, 0)),     # negated "
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # w row (lab)
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # w row (neg)
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # rand (lab)
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # rand (neg)
            pl.BlockSpec((1, yt), lambda b, c, k: (0, c)),     # cl_mask tile
            pl.BlockSpec((1, R), lambda b, c, k: (0, 0)),      # cl_mask full
            pl.BlockSpec((1, H), lambda b, c, k: (0, 0)),      # h_mask
            pl.BlockSpec((1, 2), lambda b, c, k: (0, 0),
                         memory_space=pltpu.SMEM),             # T, w_frozen
        ],
        out_specs=[
            pl.BlockSpec((bt, yt), lambda b, c, k: (b, c)),    # clause
            pl.BlockSpec((bt, H), lambda b, c, k: (b, 0)),     # class sums
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # sel (lab)
            pl.BlockSpec((bt, R), lambda b, c, k: (b, 0)),     # sel (neg)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, R), jnp.int32),
            jax.ShapeDtypeStruct((B, H), jnp.int32),
            jax.ShapeDtypeStruct((B, R), jnp.int32),
            jax.ShapeDtypeStruct((B, R), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, yt), jnp.int32),                   # violations
            pltpu.VMEM((bt, H), jnp.int32),                    # sum acc
        ],
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), need),
        interpret=interpret,
    )(neg_lit, include.astype(jnp.int8), weight_limbs(weights),
      lab_oh.astype(jnp.int32), neg_oh.astype(jnp.int32),
      w_lab.astype(jnp.int32), w_neg.astype(jnp.int32),
      rand_lab.astype(jnp.uint32), rand_neg.astype(jnp.uint32),
      # same mask twice: a (1, yt) per-tile view for the clause write and a
      # (1, R) full view for the selection masks
      cl_mask.reshape(1, R).astype(jnp.int32),
      cl_mask.reshape(1, R).astype(jnp.int32),
      h_mask.reshape(1, H).astype(jnp.int32), params)
