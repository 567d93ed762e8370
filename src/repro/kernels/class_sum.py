"""Pallas TPU kernel: partial class-sum matrix (paper Eq 2/3, Fig 4-2).

The Weight Matrix multiplies an ``m``-wide clause slice by an ``m×n`` weight
block per cycle, accumulating partial class sums over ``p=⌈c/m⌉`` iterations.
Here the k grid dimension is ``p``; each step contracts an MXU block:

    csum[b, h] += Σ_c clause[b, c] · w[h, c]

The MXU takes int8 operands (int32 accumulation) but CoTM weights are
``weight_bits``-wide (12 in the paper).  The contraction stays exact by
splitting every int32 weight into :data:`N_LIMBS` int8 limbs of
:data:`LIMB_BITS` bits — four unsigned low limbs in [0, 127] and a signed
top limb in [-8, 7] — so ``w = Σ_i limb_i · 2^(7i)`` and

    csum = Σ_i (clause · limb_iᵀ) << 7i        (int32, wraps like the ref)

Each partial product is bounded by ``C · 128`` and the shifts recombine
modulo 2^32 exactly as the int32 reference dot does.

Remainder classes are pinned by the caller to ``-2^(L_csum-1)`` (Fig 6d) via
``h_mask`` — the kernel itself only sees whole tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu_params import block_bytes, compiler_params

LIMB_BITS = 7
N_LIMBS = 5          # 4 x 7 unsigned bits + a signed 4-bit top limb = int32

_NT = (((1,), (1,)), ((), ()))      # contract the last dim of both operands


def weight_limbs(weights: jax.Array) -> jax.Array:
    """int32 weights [H, C] -> int8 limbs [N_LIMBS, H, C] with
    ``w == Σ_i limbs[i] << (LIMB_BITS * i)`` exactly."""
    w = weights.astype(jnp.int32)
    mask = (1 << LIMB_BITS) - 1
    limbs = [(w >> (LIMB_BITS * i)) & mask for i in range(N_LIMBS - 1)]
    limbs.append(w >> (LIMB_BITS * (N_LIMBS - 1)))
    return jnp.stack(limbs).astype(jnp.int8)


def limb_dot(clause_i8: jax.Array, limbs_ref) -> jax.Array:
    """In-kernel exact ``clause [bt, m] · wᵀ`` from an int8 limb block
    ``[N_LIMBS, H, m]`` -> int32 [bt, H]."""
    acc = None
    for i in range(N_LIMBS):
        part = jax.lax.dot_general(clause_i8, limbs_ref[i], _NT,
                                   preferred_element_type=jnp.int32)
        part = part << (LIMB_BITS * i) if i else part
        acc = part if acc is None else acc + part
    return acc


def _kernel(cl_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += limb_dot(cl_ref[...], w_ref)      # [bt, H]

    @pl.when(k == n_k - 1)
    def _finish():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "mt", "interpret"))
def class_sum(clauses: jax.Array, weights: jax.Array, bt: int = 8,
              mt: int = 128, interpret: bool | None = None) -> jax.Array:
    """clauses [B, C] {0,1}, weights [H, C] int -> class sums [B, H] int32.

    H rides whole in VMEM (classes are small — paper n=4); C is tiled by mt
    (the paper's m), B by bt.  ``interpret=None`` resolves through
    ``ops.resolve_interpret()`` (DTM008)."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, C = clauses.shape
    H, C2 = weights.shape
    assert C == C2 and B % bt == 0 and C % mt == 0, ((B, C, H), (bt, mt))
    grid = (B // bt, C // mt)
    need = (block_bytes(((bt, mt), 1), ((N_LIMBS, H, mt), 1), ((bt, H), 4))
            + bt * H * 4)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, mt), lambda b, k: (b, k)),
            pl.BlockSpec((N_LIMBS, H, mt), lambda b, k: (0, 0, k)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda b, k: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.int32)],
        compiler_params=compiler_params(("parallel", "arbitrary"), need),
        interpret=interpret,
    )(clauses.astype(jnp.int8), weight_limbs(weights))
