"""Pallas TPU kernel: TA Update Matrix (paper Fig 4-4, Alg 5 — the training
hot-spot).

The FPGA instantiates ``x×y`` TA-update blocks fed by y clause feedbacks and
x literals per cycle, plus one L_rand-bit random number per TA.  Kernel
mapping:

* grid (clause-tiles, literal-tiles) — each step owns one (yt, xt) TA block
  resident in VMEM (the BRAM slice of Fig 5a);
* the batch rides inside the kernel (fori), accumulating an int32 delta —
  the batched-delta training mode (DESIGN.md §2.7);
* random numbers are generated *in-kernel* from a per-element stream keyed
  on the global element index, so no [B, C, L] random tensor ever touches
  HBM (the PRNG-bandwidth insight of paper §IV-C, re-expressed: generate
  where you consume).  Two stream families share the tile body (static
  ``prng`` arg, mirrored bit-exactly by ref.stream_start/stream_advance):

  - ``counter`` — splitmix32→xorshift32 chains (TPU-native default);
  - ``lfsr``    — the paper-faithful Galois LFSR master–slave cluster
    (Fig 8): each TA cell is one lane seeded splitmix32(seed ^ key),
    advanced one Galois shift per batch element, re-seeded from an
    xorshift-advanced master every 2^lfsr_bits−1 cycles when
    ``seed_refresh`` is set — the FPGA's per-TA LFSR bank, in place.

The Conv-TM update (``ta_update_conv``) runs the same tile body and
streams with one change: every (batch element, clause row) reads the
literals of its own chosen patch, packed 32 to a word and unpacked in the
kernel, instead of one literal row shared by every clause.

Semantics (validated bit-exactly against ref.py):
  Type I  (t1): cl∧lit → +1 w.p. (s-1)/s (boost: always);
                ¬(cl∧lit) → −1 w.p. 1/s        [p_ta = ⌊2^rand_bits/s⌋]
  Type II (t2): cl∧¬lit∧¬include → +1 (deterministic)
  new_ta = clip(ta + Σ_b delta_b · l_mask, 0, n_states-1)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import stream_advance, stream_start
from .tpu_params import block_bytes, compiler_params

# per-(datapoint, clause) feedback flags, packed into one int32 code so the
# batch loop reads a single row per step: bit 0 = clause fired, bit 1 =
# Type I feedback, bit 2 = Type II feedback
_CL, _T1, _T2 = 1, 2, 4


def feedback_code(clause_out, type1, type2) -> jax.Array:
    """[B, C] {0,1} clause/Type-I/Type-II planes -> one int32 [B, C]
    code plane (bits :data:`_CL`, :data:`_T1`, :data:`_T2`)."""
    return ((clause_out > 0).astype(jnp.int32) * _CL
            + (type1 > 0).astype(jnp.int32) * _T1
            + (type2 > 0).astype(jnp.int32) * _T2)


def _tile_delta(rand, lit_row, code_col, include, p_ta, boost, delta):
    """One batch element's Alg-5 delta accumulation on a (yt, xt) tile.

    ``lit_row`` [1, xt] is the element's literal row; ``code_col``
    [yt, 1] its per-clause feedback code (:func:`feedback_code`)."""
    low = rand < p_ta                                 # P = 1/s
    clb = (code_col & _CL) != 0                       # [yt, 1]
    litb = lit_row > 0                                # [1, xt]
    t1b = (code_col & _T1) != 0
    t2b = (code_col & _T2) != 0
    cl_and_lit = jnp.logical_and(clb, litb)
    # boost: always +1 on cl∧lit; else w.p. (s-1)/s (boolean algebra, not
    # a select — Mosaic has no select on i1 vectors)
    inc1 = jnp.logical_and(cl_and_lit,
                           jnp.logical_or(boost, jnp.logical_not(low)))
    dec1 = jnp.logical_and(jnp.logical_not(cl_and_lit), low)
    d1 = inc1.astype(jnp.int32) - dec1.astype(jnp.int32)
    inc2 = jnp.logical_and(jnp.logical_and(clb, jnp.logical_not(litb)),
                           jnp.logical_not(include)).astype(jnp.int32)
    return delta + jnp.where(t1b, d1, 0) + jnp.where(t2b, inc2, 0)


def _batch_rows(lit_ref, code_ref, b):
    """Row ``b`` of the literal block ([1, xt]) and of the feedback-code
    block, turned into a clause column ([yt, 1]) — ref-level dynamic
    sublane reads, the form Mosaic lowers."""
    return lit_ref[pl.ds(b, 1), :], code_ref[pl.ds(b, 1), :].T


def _conv_rows(words_ref, code_ref, b, *, n_lit: int, xt: int):
    """Element ``b``'s literal tile [yt, xt], one literal row per clause
    row (the patch that row is updated against), and its feedback-code
    column [yt, 1].  ``words_ref`` [n_lit, xt/32, yt] is word-major, so
    each word of the tile is one [1, yt] row turned into a clause column
    (the same dynamic read as :func:`_batch_rows`) and spread over its 32
    lanes; element ``b`` reads literal block ``b % n_lit`` (both feedback
    rounds of a datapoint share its patch choice)."""
    e = jax.lax.rem(b, n_lit)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, xt), 1)
    word, bit = lane >> 5, lane & 31
    lit = None
    for w in range(xt // 32):
        col = words_ref[e, pl.ds(w, 1), :].T                  # [yt, 1]
        lit = col if lit is None else jnp.where(word == w, col, lit)
    return (lit >> bit) & 1, code_ref[pl.ds(b, 1), :].T


def _tile_update(ci, li, ta_ref, lit_ref, code_ref, lmask_ref, params_ref,
                 out_ref, *, batch: int, n_l_tiles: int, yt: int, xt: int,
                 rand_bits: int, prng: str = "counter",
                 lfsr_bits: int = 24, seed_refresh: bool = True,
                 rows=_batch_rows):
    """Shared (yt, xt) TA-tile update body.

    ``ci``/``li`` are the tile's GLOBAL grid coordinates — the dense kernel
    passes its program ids, the sparse kernel passes the gathered tile's
    original row index so the per-element PRNG streams are identical to
    a dense launch (bit-exact clause-skip compaction).  ``params_ref[0, 4]``
    is a global ROW offset added on top (uint32, usually 0): a clause shard
    holding rows [row0, row0 + C_loc) of a larger machine keys its streams
    at the rows' global numbers, so a sharded update is bit-identical to
    the same rows of a single-device launch.

    ``prng``/``lfsr_bits``/``seed_refresh`` select the stream family
    (module docstring); all stream state lives in registers/VMEM — only
    the uint32 master seed crosses from SMEM.  ``rows(lit_ref, code_ref,
    b)`` reads batch element ``b``'s literals and feedback codes:
    :func:`_batch_rows` (one literal row shared by every clause) or, for
    the conv update, :func:`_conv_rows`."""
    # dynamic model scalars ride in SMEM — a DTMProgram swap or a fresh
    # per-step seed never retraces (cache-size == 1 semantics, §IV-D-a).
    seed = params_ref[0, 0]
    p_ta = params_ref[0, 1]
    boost = params_ref[0, 2] > 0
    n_states = params_ref[0, 3].astype(jnp.int32)
    row0 = params_ref[0, 4]
    ta = ta_ref[...]                                      # [yt, xt] int32
    include = ta >= (n_states >> 1)

    # per-element stream keyed on GLOBAL element index — the result is
    # tile-layout independent (ref.py reproduces it exactly).
    gy = (ci.astype(jnp.uint32) * yt + row0
          + jax.lax.broadcasted_iota(jnp.uint32, (yt, xt), 0))
    gx = (li.astype(jnp.uint32) * xt
          + jax.lax.broadcasted_iota(jnp.uint32, (yt, xt), 1))
    key = gy * jnp.uint32(n_l_tiles * xt) + gx
    st0 = stream_start(seed, key, prng, lfsr_bits)

    def body(b, carry):
        st, delta = carry
        st, rand = stream_advance(st, key, prng, lfsr_bits, seed_refresh,
                                  rand_bits)
        lit_row, code_col = rows(lit_ref, code_ref, b)
        delta = _tile_delta(rand, lit_row, code_col, include, p_ta, boost,
                            delta)
        return st, delta

    delta = jnp.zeros((yt, xt), jnp.int32)
    _, delta = jax.lax.fori_loop(0, batch, body, (st0, delta))
    delta = delta * lmask_ref[...]                        # Fig 6a inverse mask
    out_ref[...] = jnp.clip(ta + delta, 0, n_states - 1)


def _kernel(ta_ref, lit_ref, code_ref, lmask_ref, params_ref, out_ref, *,
            batch: int, n_l_tiles: int, yt: int, xt: int, rand_bits: int,
            prng: str, lfsr_bits: int, seed_refresh: bool):
    _tile_update(pl.program_id(0), pl.program_id(1), ta_ref, lit_ref,
                 code_ref, lmask_ref, params_ref, out_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh)


def _sparse_kernel(idx_ref, params_ref, ta_ref, lit_ref, code_ref,
                   lmask_ref, out_ref, *, batch: int, n_l_tiles: int,
                   yt: int, xt: int, rand_bits: int, prng: str,
                   lfsr_bits: int, seed_refresh: bool):
    """Compacted grid step: slot ``program_id(0)`` owns the ACTIVE clause
    tile whose original row-tile index is ``idx_ref[program_id(0)]`` (the
    scalar-prefetch index vector also drives the BlockSpec gathers).  The
    PRNG stream is keyed on the original tile coordinates, so the update
    is bit-identical to the dense kernel's for that tile."""
    _tile_update(idx_ref[pl.program_id(0)], pl.program_id(1), ta_ref,
                 lit_ref, code_ref, lmask_ref, params_ref, out_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh)


def _conv_kernel(ta_ref, words_ref, code_ref, lmask_ref, params_ref,
                 out_ref, *, batch: int, n_lit: int, n_l_tiles: int, yt: int,
                 xt: int, rand_bits: int, prng: str, lfsr_bits: int,
                 seed_refresh: bool):
    """Dense conv grid step: the flat tile body, with each clause row's
    literals read from its own chosen patch (:func:`_conv_rows`)."""
    _tile_update(pl.program_id(0), pl.program_id(1), ta_ref, words_ref,
                 code_ref, lmask_ref, params_ref, out_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh,
                 rows=functools.partial(_conv_rows, n_lit=n_lit, xt=xt))


def _streamed_kernel(ta_ref, lit_ref, code_ref, lmask_ref, rand_ref,
                     params_ref, out_ref, *, batch: int, yt: int, xt: int):
    """Streamed-rand baseline: the same tile body, but the randoms arrive
    as a pre-materialised [B, yt, xt] uint32 block from HBM
    (ref.ta_rand_stream) — exactly the traffic the in-kernel generator
    eliminates.  Kept as a dispatchable path so the win is measurable on
    one machine (benchmarks/fig15_lfsr.py) and streamed-vs-in-kernel
    bit-identity is a test, not a claim."""
    p_ta = params_ref[0, 1]
    boost = params_ref[0, 2] > 0
    n_states = params_ref[0, 3].astype(jnp.int32)
    ta = ta_ref[...]                                      # [yt, xt] int32
    include = ta >= (n_states >> 1)

    def body(b, delta):
        lit_row, code_col = _batch_rows(lit_ref, code_ref, b)
        return _tile_delta(rand_ref[b], lit_row, code_col, include, p_ta,
                           boost, delta)

    delta = jax.lax.fori_loop(0, batch, body,
                              jnp.zeros((yt, xt), jnp.int32))
    delta = delta * lmask_ref[...]
    out_ref[...] = jnp.clip(ta + delta, 0, n_states - 1)


def _vmem_need(B: int, yt: int, xt: int, rands: bool = False) -> int:
    """Per-step VMEM of the TA-update kernels: TA tile in + out, literal
    rows, feedback codes, l_mask (+ the streamed rand block)."""
    blocks = [((yt, xt), 4), ((B, xt), 4), ((B, yt), 4), ((1, xt), 4),
              ((yt, xt), 4)]
    if rands:
        blocks.append(((B, yt, xt), 4))
    return block_bytes(*blocks)


def _params(seed, p_ta, boost, n_states, row0):
    return jnp.stack([
        jnp.asarray(seed, jnp.uint32),
        jnp.asarray(p_ta, jnp.uint32),
        jnp.asarray(boost, jnp.uint32),
        jnp.asarray(n_states, jnp.uint32),
        jnp.asarray(row0, jnp.uint32),
    ]).reshape(1, 5)


@functools.partial(jax.jit, static_argnames=("rand_bits", "yt", "xt",
                                             "prng", "lfsr_bits",
                                             "seed_refresh", "interpret"))
def ta_update_sparse(ta: jax.Array, literals: jax.Array,
                     clause_out: jax.Array, type1: jax.Array,
                     type2: jax.Array, l_mask: jax.Array,
                     tile_idx: jax.Array, seed, p_ta, rand_bits: int = 16,
                     boost=True, n_states=256, yt: int = 128, xt: int = 256,
                     row0=0, prng: str = "counter", lfsr_bits: int = 24,
                     seed_refresh: bool = True,
                     interpret: bool | None = None) -> jax.Array:
    """Compacted TA update over the ACTIVE clause tiles only (Alg 6 made
    real): ``tile_idx`` [k] int32 lists the row-tile indices to update and
    doubles as the scalar-prefetch index vector — every BlockSpec gathers
    its (yt-high) tile through it, so only k of the C//yt clause tiles ever
    move between HBM and VMEM (the paper's skipped BRAM traffic).

    Returns the COMPACTED updated tiles [k*yt, L] int32 (slot i holds
    original rows ``tile_idx[i]*yt : (tile_idx[i]+1)*yt``); the caller
    scatters them back (ops.ta_update_compact_op).  Bit-identical to the
    dense kernel on the gathered tiles — the PRNG stream is keyed on each
    tile's ORIGINAL row index via the prefetched vector.  Duplicate
    entries in ``tile_idx`` (capacity-bucket fill slots) are harmless:
    they recompute the same tile with the same streams.

    ``row0`` (traced uint32 scalar, default 0) offsets every stream key's
    global row number — clause shards pass their first global row so the
    sharded update matches a single-device launch bit-for-bit.

    ``prng``/``lfsr_bits``/``seed_refresh`` select the in-kernel stream
    family (static; see module docstring).

    ``interpret=None`` (default) resolves through
    ``ops.resolve_interpret()`` like every other kernel, so direct
    callers on TPU get the compiled path."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    C, L = ta.shape
    B = literals.shape[0]
    k = tile_idx.shape[0]
    assert C % yt == 0 and L % xt == 0, ((C, L), (yt, xt))
    grid = (k, L // xt)
    params = _params(seed, p_ta, boost, n_states, row0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # (tile_idx, params)
        grid=grid,
        in_specs=[
            pl.BlockSpec((yt, xt), lambda c, l, idx, prm: (idx[c], l)),
            pl.BlockSpec((B, xt), lambda c, l, idx, prm: (0, l)),
            pl.BlockSpec((B, yt), lambda c, l, idx, prm: (0, idx[c])),
            pl.BlockSpec((1, xt), lambda c, l, idx, prm: (0, l)),
        ],
        out_specs=pl.BlockSpec((yt, xt), lambda c, l, idx, prm: (c, l)),
    )
    return pl.pallas_call(
        functools.partial(_sparse_kernel, batch=B, n_l_tiles=grid[1], yt=yt,
                          xt=xt, rand_bits=rand_bits, prng=prng,
                          lfsr_bits=lfsr_bits, seed_refresh=seed_refresh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k * yt, L), jnp.int32),
        compiler_params=compiler_params(("parallel", "parallel"),
                                        _vmem_need(B, yt, xt)),
        interpret=interpret,
    )(tile_idx.astype(jnp.int32), params,
      ta.astype(jnp.int32), literals.astype(jnp.int32),
      feedback_code(clause_out, type1, type2),
      l_mask.reshape(1, L).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("rand_bits", "yt", "xt",
                                             "prng", "lfsr_bits",
                                             "seed_refresh", "interpret"))
def ta_update(ta: jax.Array, literals: jax.Array, clause_out: jax.Array,
              type1: jax.Array, type2: jax.Array, l_mask: jax.Array,
              seed, p_ta, rand_bits: int = 16, boost=True,
              n_states=256, yt: int = 128, xt: int = 256, row0=0,
              prng: str = "counter", lfsr_bits: int = 24,
              seed_refresh: bool = True,
              interpret: bool | None = None) -> jax.Array:
    """Batched TA update.

    ta [C, L] any int dtype (the engine stores uint8-narrowed states, 4 per
    32-bit word; widened to int32 on entry), literals [B, L] {0,1},
    clause_out/type1/type2 [B, C] {0,1}, l_mask [L] {0,1} -> new ta [C, L]
    int32.  ``seed``/``p_ta``/``boost``/``n_states``/``row0`` may be traced
    scalars (they ride in SMEM).  ``row0`` offsets the PRNG stream keys'
    global row numbers (clause-sharded execution — see ``_tile_update``).
    ``prng``/``lfsr_bits``/``seed_refresh`` select the in-kernel stream
    family (static; see module docstring).
    ``ops.ta_update_op(emit_include=True)`` fuses the packed
    include-bitplane emission onto this kernel's output.
    ``interpret=None`` resolves through ``ops.resolve_interpret()``
    (DTM008)."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    C, L = ta.shape
    B = literals.shape[0]
    assert C % yt == 0 and L % xt == 0, ((C, L), (yt, xt))
    grid = (C // yt, L // xt)
    params = _params(seed, p_ta, boost, n_states, row0)
    return pl.pallas_call(
        functools.partial(_kernel, batch=B, n_l_tiles=grid[1], yt=yt, xt=xt,
                          rand_bits=rand_bits, prng=prng,
                          lfsr_bits=lfsr_bits, seed_refresh=seed_refresh),
        grid=grid,
        in_specs=[
            pl.BlockSpec((yt, xt), lambda c, l: (c, l)),       # ta
            pl.BlockSpec((B, xt), lambda c, l: (0, l)),        # literals
            pl.BlockSpec((B, yt), lambda c, l: (0, c)),        # fb codes
            pl.BlockSpec((1, xt), lambda c, l: (0, l)),        # l_mask
            pl.BlockSpec((1, 5), lambda c, l: (0, 0),
                         memory_space=pltpu.SMEM),             # scalars
        ],
        out_specs=pl.BlockSpec((yt, xt), lambda c, l: (c, l)),
        out_shape=jax.ShapeDtypeStruct((C, L), jnp.int32),
        compiler_params=compiler_params(("parallel", "parallel"),
                                        _vmem_need(B, yt, xt)),
        interpret=interpret,
    )(ta.astype(jnp.int32), literals.astype(jnp.int32),
      feedback_code(clause_out, type1, type2),
      l_mask.reshape(1, L).astype(jnp.int32), params)


@functools.partial(jax.jit, static_argnames=("yt", "xt", "interpret"))
def ta_update_streamed(ta: jax.Array, literals: jax.Array,
                       clause_out: jax.Array, type1: jax.Array,
                       type2: jax.Array, l_mask: jax.Array,
                       rands: jax.Array, p_ta, boost=True, n_states=256,
                       yt: int = 128, xt: int = 256,
                       interpret: bool | None = None) -> jax.Array:
    """Batched TA update consuming PRE-MATERIALISED randoms ``rands``
    [B, C, L] uint32 (ref.ta_rand_stream) — the streamed baseline the
    in-kernel generator replaces.  Bit-identical to ``ta_update`` when the
    stream was generated with the same keying; moves B·C·L·4 extra bytes
    per step, which fig15_lfsr measures.  ``interpret=None`` resolves
    through ``ops.resolve_interpret()`` (DTM008)."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    C, L = ta.shape
    B = literals.shape[0]
    assert C % yt == 0 and L % xt == 0, ((C, L), (yt, xt))
    assert rands.shape == (B, C, L), (rands.shape, (B, C, L))
    grid = (C // yt, L // xt)
    params = _params(0, p_ta, boost, n_states, 0)
    return pl.pallas_call(
        functools.partial(_streamed_kernel, batch=B, yt=yt, xt=xt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((yt, xt), lambda c, l: (c, l)),       # ta
            pl.BlockSpec((B, xt), lambda c, l: (0, l)),        # literals
            pl.BlockSpec((B, yt), lambda c, l: (0, c)),        # fb codes
            pl.BlockSpec((1, xt), lambda c, l: (0, l)),        # l_mask
            pl.BlockSpec((B, yt, xt), lambda c, l: (0, c, l)), # rands
            pl.BlockSpec((1, 5), lambda c, l: (0, 0),
                         memory_space=pltpu.SMEM),             # scalars
        ],
        out_specs=pl.BlockSpec((yt, xt), lambda c, l: (c, l)),
        out_shape=jax.ShapeDtypeStruct((C, L), jnp.int32),
        compiler_params=compiler_params(("parallel", "parallel"),
                                        _vmem_need(B, yt, xt, rands=True)),
        interpret=interpret,
    )(ta.astype(jnp.int32), literals.astype(jnp.int32),
      feedback_code(clause_out, type1, type2),
      l_mask.reshape(1, L).astype(jnp.int32), rands.astype(jnp.uint32),
      params)


@functools.partial(jax.jit, static_argnames=("rand_bits", "yt", "xt",
                                             "prng", "lfsr_bits",
                                             "seed_refresh", "interpret"))
def ta_update_conv(ta: jax.Array, lit_words: jax.Array,
                   clause_out: jax.Array, type1: jax.Array,
                   type2: jax.Array, l_mask: jax.Array, seed, p_ta,
                   rand_bits: int = 16, boost=True, n_states=256,
                   yt: int = 128, xt: int = 256, row0=0,
                   prng: str = "counter", lfsr_bits: int = 24,
                   seed_refresh: bool = True,
                   interpret: bool | None = None) -> jax.Array:
    """Batched Conv-TM TA update: :func:`ta_update` with a literal row of
    its own for every (batch element, clause row).

    ``lit_words`` uint32 [Bl, C, L/32] holds the packed literals of the
    patch each clause row of datapoint ``b`` is updated against (the
    engine gathers them from the staged [B, P, W] patches by the chosen
    patch index — no [B, C, L] tensor, no one-hot matmul);
    ``clause_out``/``type1``/``type2`` are [E, C] over the stacked
    feedback rounds (E = 2·Bl, target rounds first), and element ``e``
    reads literal block ``e % Bl``.  The in-kernel streams are keyed,
    seeded and advanced exactly as :func:`ta_update`'s (global row
    ``row0 + row`` times the padded row width, plus the column), so a
    conv TA cell sees the random numbers a flat one does.  Returns new
    ta [C, L] int32; ``ops.ta_update_conv_op`` pads and emits the packed
    include bitplane.  Oracle: ``ref.ta_update_conv_ref``."""
    if interpret is None:
        from .ops import resolve_interpret     # local: ops imports us
        interpret = resolve_interpret()
    C, L = ta.shape
    E, Bl = clause_out.shape[0], lit_words.shape[0]
    assert C % yt == 0 and L % xt == 0 and xt % 32 == 0, ((C, L), (yt, xt))
    assert lit_words.shape == (Bl, C, L // 32), (lit_words.shape, (C, L))
    # word-major so a tile's words are (8, 128)-aligned rows of the block
    words = jax.lax.bitcast_convert_type(lit_words, jnp.int32).transpose(
        0, 2, 1)                                            # [Bl, L/32, C]
    grid = (C // yt, L // xt)
    params = _params(seed, p_ta, boost, n_states, row0)
    need = block_bytes(((yt, xt), 4), ((Bl, xt // 32, yt), 4), ((E, yt), 4),
                       ((1, xt), 4), ((yt, xt), 4))
    return pl.pallas_call(
        functools.partial(_conv_kernel, batch=E, n_lit=Bl,
                          n_l_tiles=grid[1], yt=yt, xt=xt,
                          rand_bits=rand_bits, prng=prng,
                          lfsr_bits=lfsr_bits, seed_refresh=seed_refresh),
        grid=grid,
        in_specs=[
            pl.BlockSpec((yt, xt), lambda c, l: (c, l)),       # ta
            pl.BlockSpec((Bl, xt // 32, yt),
                         lambda c, l: (0, l, c)),              # lit words
            pl.BlockSpec((E, yt), lambda c, l: (0, c)),        # fb codes
            pl.BlockSpec((1, xt), lambda c, l: (0, l)),        # l_mask
            pl.BlockSpec((1, 5), lambda c, l: (0, 0),
                         memory_space=pltpu.SMEM),             # scalars
        ],
        out_specs=pl.BlockSpec((yt, xt), lambda c, l: (c, l)),
        out_shape=jax.ShapeDtypeStruct((C, L), jnp.int32),
        compiler_params=compiler_params(("parallel", "parallel"), need),
        interpret=interpret,
    )(ta.astype(jnp.int32), words, feedback_code(clause_out, type1, type2),
      l_mask.reshape(1, L).astype(jnp.int32), params)
