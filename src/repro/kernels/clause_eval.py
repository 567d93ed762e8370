"""Pallas TPU kernel: MXU-tiled clause evaluation (DESIGN.md §2.1/2.3).

The paper's Clause Matrix (Fig 4-1, Fig 5a) streams ``x×y`` slices of TA
actions from BRAM and AND-folds them against the literal buffer over
``a=⌈2f/x⌉ · b=⌈c/y⌉`` iterations.  Here each Pallas grid step streams one
``(y_tile, x_tile)`` include-matrix block HBM→VMEM and contracts it on the
MXU against a ``(b_tile, x_tile)`` block of *negated* literals:

    violations[b, c] = Σ_l include[c, l] · (1 - literal[b, l])
    clause[b, c]     = (violations == 0) ∧ (nonempty ∨ training)

The k (literal) grid dimension is the paper's ``a`` iteration; remainder
masking (Fig 6a/6b) is done by zero-padding: a zero include column can never
violate, and padded clause rows are invalidated by the caller's cl_mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu_params import block_bytes, compiler_params


def _kernel(neg_lit_ref, inc_ref, out_ref, acc_ref, cnt_ref, *,
            n_k: int, eval_mode: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    inc = inc_ref[...]                                # [yt, xt] int8
    # violations: contract the literal (x) axis on the MXU — int8
    # operands, int32 accumulation
    acc_ref[...] += jax.lax.dot_general(
        neg_lit_ref[...], inc, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)             # [bt, yt]
    if eval_mode:
        cnt_ref[...] += inc.astype(jnp.int32).sum(axis=1, keepdims=True).T

    @pl.when(k == n_k - 1)
    def _finish():
        fired = acc_ref[...] == 0
        if eval_mode:
            fired = jnp.logical_and(fired, cnt_ref[...] > 0)
        out_ref[...] = fired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("eval_mode", "bt", "yt", "xt",
                                             "interpret"))
def clause_eval(literals: jax.Array, include: jax.Array,
                eval_mode: bool = False, bt: int = 8, yt: int = 128,
                xt: int = 256, interpret: bool | None = None) -> jax.Array:
    """literals [B, L] {0,1}, include [C, L] {0,1} -> clause [B, C] int32.

    B, C, L must be multiples of (bt, yt, xt) — callers pad (the DTM engine's
    buffers already are).  ``interpret=None`` resolves through
    ``ops.resolve_interpret()`` (DTM008)."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, L = literals.shape
    C, L2 = include.shape
    assert L == L2 and B % bt == 0 and C % yt == 0 and L % xt == 0, (
        (B, C, L), (bt, yt, xt))
    neg = (1 - literals).astype(jnp.int8)
    grid = (B // bt, C // yt, L // xt)
    need = (block_bytes(((bt, xt), 1), ((yt, xt), 1), ((bt, yt), 4))
            + (bt * yt + yt) * 4)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2], eval_mode=eval_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, xt), lambda b, c, k: (b, k)),
            pl.BlockSpec((yt, xt), lambda b, c, k: (c, k)),
        ],
        out_specs=pl.BlockSpec((bt, yt), lambda b, c, k: (b, c)),
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bt, yt), jnp.int32),
            pltpu.VMEM((1, yt), jnp.int32),
        ],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), need),
        interpret=interpret,
    )(neg, include.astype(jnp.int8))
