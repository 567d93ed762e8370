"""Pallas TPU kernels: bit-packed clause evaluation (VPU + MXU paths).

Direct analogue of the paper's LUT mapping (Fig 4-6): literals and TA
include-actions are packed 32-per-word; a clause fires iff every packed word
satisfies ``(~inc | lit) == ~0`` ⇔ ``(inc & ~lit) == 0``.

Two legs, bit-identical outputs, dispatched by batch size (autotune.py /
select_path):

* ``packed_clause_eval`` — pure VPU word-OR reduction, no MXU work at all;
  the right choice for tiny batches (the edge single-datapoint regime the
  FPGA targets) where a matmul recast wastes systolic occupancy.

      viol_or[b, c] = OR_w ( inc[c, w] & ~lit[b, w] )
      clause[b, c]  = (viol_or == 0) ∧ (nonempty ∨ training)

* ``packed_clause_eval_mxu`` — popcount-as-matmul: each uint32 word is
  expanded in-register to 32 int8 bitplanes and the violation count
  becomes an int8·int8→int32 dot product,

      viol[b, c] = Σ_l inc_bits[c, l] · (1 − lit_bits[b, l]),
      clause[b, c] = (viol == 0) ∧ (nonempty ∨ training),

  which the MXU executes at matmul rates — large-batch packed eval stops
  being VPU-bound (the all-popcount datapath of the 65-nm accelerator
  paper, arXiv 2501.19347, recast onto the systolic array).  Still reads
  the ~8x-smaller packed operands from HBM; the expansion never leaves
  VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu_params import block_bytes, compiler_params


def _row_any(words):
    """[n, w] uint32 -> [n, 1] int32: 1 iff any word of the row is
    nonzero (an OR-reduction expressed as the max-reduce Mosaic lowers)."""
    return jnp.max((words != 0).astype(jnp.int32), axis=1, keepdims=True)


def _kernel(lit_ref, inc_ref, out_ref, viol_ref, ne_ref, *,
            batch_tile: int, n_k: int, eval_mode: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        viol_ref[...] = jnp.zeros_like(viol_ref)
        ne_ref[...] = jnp.zeros_like(ne_ref)

    inc = inc_ref[...]                                 # [yt, wt] uint32
    # hoisted per-word nonempty reduction: one OR over the include tile
    # serves both the eval-mode nonempty check and an all-exclude skip —
    # a tile of zero include words can neither violate nor fire-gate, so
    # the whole per-batch violation loop is skipped (exclude-dominated
    # clauses are the common converged case; Fig 4-6 frugality)
    nonempty = _row_any(inc).T                         # [1, yt]

    @pl.when(jnp.max(nonempty) > 0)
    def _accumulate():
        ne_ref[...] = jnp.maximum(ne_ref[...], nonempty)
        # static batch loop: row b of the literal tile against the whole
        # include tile, the per-clause OR landing in row b of viol
        for b in range(batch_tile):
            v = jnp.bitwise_and(inc, jnp.bitwise_not(lit_ref[b:b + 1, :]))
            viol_ref[b:b + 1, :] = jnp.maximum(viol_ref[b:b + 1, :],
                                               _row_any(v).T)

    @pl.when(k == n_k - 1)
    def _finish():
        fired = viol_ref[...] == 0
        if eval_mode:
            fired = jnp.logical_and(fired, ne_ref[...] != 0)
        out_ref[...] = fired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("eval_mode", "bt", "yt", "wt",
                                             "interpret"))
def packed_clause_eval(packed_literals: jax.Array, packed_include: jax.Array,
                       eval_mode: bool = False, bt: int = 8, yt: int = 128,
                       wt: int = 128,
                       interpret: bool | None = None) -> jax.Array:
    """packed_literals [B, W] uint32, packed_include [C, W] uint32
    -> clause [B, C] int32.  W = ceil(L/32), padded to wt multiples with
    zero words (zero include words never violate).

    ``interpret=None`` (default) resolves through
    ``ops.resolve_interpret()`` like every other kernel — direct callers
    get the compiled TPU path on TPU instead of a silently interpreted
    one (read at trace time; flip ``REPRO_INTERPRET`` before first call).

    Tail-bit contract: bits at positions >= L in the last real word of
    ``packed_include`` MUST be zero — they would otherwise veto clauses
    (and fake nonempty ones in eval mode).  ``ops.packed_clause_eval_op``
    enforces this via its ``n_bits`` argument (ref.tail_mask_words);
    callers going straight to this kernel own the masking themselves."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, W = packed_literals.shape
    C, W2 = packed_include.shape
    assert W == W2 and B % bt == 0 and C % yt == 0 and W % wt == 0, (
        (B, C, W), (bt, yt, wt))
    grid = (B // bt, C // yt, W // wt)
    need = (block_bytes(((bt, wt), 4), ((yt, wt), 4), ((bt, yt), 4))
            + (bt * yt + yt) * 4)
    return pl.pallas_call(
        functools.partial(_kernel, batch_tile=bt, n_k=grid[2],
                          eval_mode=eval_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, wt), lambda b, c, k: (b, k)),
            pl.BlockSpec((yt, wt), lambda b, c, k: (c, k)),
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
    )(packed_literals.astype(jnp.uint32), packed_include.astype(jnp.uint32))


def _bitplane(words, j: int):
    """Bit ``j`` of every word, as an int8 {0,1} plane of the same shape
    (stays in VMEM; the bit arithmetic runs at 32 bits — the v5e VPU has
    no int8 ALU — and only the finished plane narrows to int8)."""
    return ((words >> jnp.uint32(j)) & jnp.uint32(1)).astype(jnp.int8)


def _mxu_kernel(lit_ref, inc_ref, out_ref, viol_ref, ne_ref, *,
                n_k: int, eval_mode: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        viol_ref[...] = jnp.zeros_like(viol_ref)
        ne_ref[...] = jnp.zeros_like(ne_ref)

    inc = inc_ref[...]                                 # [yt, wt] uint32
    lit = lit_ref[...]                                 # [bt, wt] uint32
    if eval_mode:
        ne_ref[...] = jnp.maximum(ne_ref[...], _row_any(inc).T)
    # violations as int8 matmuls, one per bit position j:
    # Σ_j (~lit)_j [bt, wt] · inc_jᵀ [wt, yt], with (~lit)_j = 1 - lit_j.
    # The count sums over every (word, bit) pair, so walking the
    # bitplanes plane-major gives the same total as the word-major
    # expansion; zero-padded words contribute nothing on either side.
    acc = viol_ref[...]
    for j in range(32):
        acc += jax.lax.dot_general(
            _bitplane(~lit, j), _bitplane(inc, j),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
    viol_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _finish():
        fired = viol_ref[...] == 0
        if eval_mode:
            fired = jnp.logical_and(fired, ne_ref[...] != 0)
        out_ref[...] = fired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("eval_mode", "bt", "yt", "wt",
                                             "interpret"))
def packed_clause_eval_mxu(packed_literals: jax.Array,
                           packed_include: jax.Array,
                           eval_mode: bool = False, bt: int = 8,
                           yt: int = 128, wt: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """MXU popcount leg: same contract as :func:`packed_clause_eval`
    (packed [B, W] × [C, W] uint32 -> clause [B, C] int32, identical tail-
    bit obligations), violations computed as int8 dot products over
    in-register bitplane expansions: 32 int8 dot products per grid step,
    one per bit position, each contracting ``wt`` words."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    B, W = packed_literals.shape
    C, W2 = packed_include.shape
    assert W == W2 and B % bt == 0 and C % yt == 0 and W % wt == 0, (
        (B, C, W), (bt, yt, wt))
    grid = (B // bt, C // yt, W // wt)
    need = (block_bytes(((bt, wt), 4), ((yt, wt), 4), ((bt, yt), 4))
            + (bt * yt + yt) * 4)
    return pl.pallas_call(
        functools.partial(_mxu_kernel, n_k=grid[2],
                          eval_mode=eval_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, wt), lambda b, c, k: (b, k)),
            pl.BlockSpec((yt, wt), lambda b, c, k: (c, k)),
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
    )(packed_literals.astype(jnp.uint32), packed_include.astype(jnp.uint32))
