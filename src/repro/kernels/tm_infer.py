"""Pallas TPU kernel: FUSED TM inference (paper Fig 9a pipeline).

The FPGA pipelines the Clause Matrix into the Weight Matrix: as soon as a
group of y clause outputs lands in the clause buffer, the weight matrix
starts consuming it.  The fused kernel does the same inside VMEM — clause
tiles never round-trip to HBM:

  for c-tile:                      (grid dim 1)
    for k-tile:                    (grid dim 2, literal slices)
      viol += (1-lit)ᵀ·inc         (MXU)
    clause_tile = (viol == 0)      (VPU, stays in VMEM)
    csum  += clause_tile · wᵀ      (MXU)
  out = csum                       (written once per batch tile)

This removes the [B, C] clause-output HBM traffic of the two-kernel path —
the memory-roofline win measured in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .class_sum import N_LIMBS, limb_dot, weight_limbs
from .tpu_params import block_bytes, compiler_params


def _kernel(neg_lit_ref, inc_ref, w_ref, out_ref, viol_ref, cnt_ref, acc_ref,
            *, n_c: int, n_k: int, eval_mode: bool):
    c, k = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(c == 0, k == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k == 0)
    def _init_viol():
        viol_ref[...] = jnp.zeros_like(viol_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    inc = inc_ref[...]                                # [yt, xt] int8
    viol_ref[...] += jax.lax.dot_general(
        neg_lit_ref[...], inc, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)             # [bt, yt]
    if eval_mode:
        cnt_ref[...] += inc.astype(jnp.int32).sum(axis=1, keepdims=True).T

    @pl.when(k == n_k - 1)
    def _consume_clause_tile():
        fired = viol_ref[...] == 0
        if eval_mode:
            fired = jnp.logical_and(fired, cnt_ref[...] > 0)
        clause = fired.astype(jnp.int8)               # [bt, yt] — VMEM only
        acc_ref[...] += limb_dot(clause, w_ref)       # [bt, H]

        @pl.when(c == n_c - 1)
        def _emit():
            out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("eval_mode", "bt", "yt", "xt",
                                             "interpret"))
def tm_infer(literals: jax.Array, include: jax.Array, weights: jax.Array,
             eval_mode: bool = True, bt: int = 8, yt: int = 128,
             xt: int = 256, interpret: bool | None = None) -> jax.Array:
    """Fused inference: literals [B,L], include [C,L], weights [H,C]
    -> class sums [B,H] int32.  Dims must tile (callers pad).
    ``interpret=None`` resolves through ``ops.resolve_interpret()``
    (DTM008)."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, L = literals.shape
    C, L2 = include.shape
    H, C2 = weights.shape
    assert L == L2 and C == C2
    assert B % bt == 0 and C % yt == 0 and L % xt == 0, ((B, C, L, H),
                                                         (bt, yt, xt))
    neg = (1 - literals).astype(jnp.int8)
    grid = (B // bt, C // yt, L // xt)
    need = (block_bytes(((bt, xt), 1), ((yt, xt), 1), ((N_LIMBS, H, yt), 1),
                        ((bt, H), 4))
            + (bt * yt + yt + bt * H) * 4)
    return pl.pallas_call(
        functools.partial(_kernel, n_c=grid[1], n_k=grid[2],
                          eval_mode=eval_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, xt), lambda b, c, k: (b, k)),
            pl.BlockSpec((yt, xt), lambda b, c, k: (c, k)),
            pl.BlockSpec((N_LIMBS, H, yt), lambda b, c, k: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda b, c, k: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bt, yt), jnp.int32),
            pltpu.VMEM((1, yt), jnp.int32),
            pltpu.VMEM((bt, H), jnp.int32),
        ],
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), need),
        interpret=interpret,
    )(neg, include.astype(jnp.int8), weight_limbs(weights))
