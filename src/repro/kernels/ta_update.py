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
  ``prng`` arg, mirrored bit-exactly by ref.stream_start/stream_step):

  - ``counter`` — splitmix32→xorshift32 chains (TPU-native default);
  - ``lfsr``    — the paper-faithful Galois LFSR master–slave cluster
    (Fig 8): each TA cell is one lane seeded splitmix32(seed ^ key),
    advanced one Galois shift per batch element, re-seeded from an
    xorshift-advanced master every 2^lfsr_bits−1 cycles when
    ``seed_refresh`` is set — the FPGA's per-TA LFSR bank, in place.

The tile body (:func:`_tile_update`) spends four VPU ops per (element, TA
cell) beyond the stream's own step:

* an element's feedback-code row is decoded on the row (one vreg an op)
  into four event bits per clause, packed at the bit offsets of four
  counters (:func:`_event_tile`), and turned into clause rows by a
  transpose.  These event tiles depend on the clause rows alone: they are
  built once per clause tile into VMEM scratch and reused by every literal
  tile (grid axis 1 runs in order);
* per cell the draw (``lanes < p_ta << (lfsr_bits − rand_bits)`` on the
  raw LFSR word, or the emitted word for the counter chains and for an
  LFSR narrower than the comparison) and the literal select which
  counters the cell's event bits add to (:func:`_tile_delta`); a run of
  at most :data:`_RUN` elements is decoded into the delta at its end
  (:func:`_decode`);
* the batch loop runs per strip of :data:`_STRIP` rows, so a strip's
  LFSR lanes and counters stay in vector registers across it;
* the master–slave re-seed is compiled in only when the static batch
  reaches the 2^L−1 period.

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import stream_start, stream_step, stream_word
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


# The batch loop counts four kinds of event per TA cell in one int32
# word, one byte each: Type I +1 (byte 0), the 1/s −1 where the literal is
# 1 (byte 1), Type II +1 (byte 2), the 1/s −1 where the literal is 0
# (byte 3).  A run of at most _RUN elements cannot overflow a byte; it is
# then decoded into the delta.  _RUN also bounds the event-tile scratch
# (8 MiB at yt=128).
_RUN = 128
# TA rows one batch loop carries: a strip's LFSR lanes and counts (2 x 8
# vregs at xt=256) stay in registers across the loop, where a whole
# 128-row tile's (2 x 32) would fill the 64 vregs and spill.
_STRIP = 32
# The conv update unpacks each strip's literals anew (word transposes per
# strip), so it takes fewer, taller strips.
_CONV_STRIP = 64


def _event_tile(code_row, keep, lanes: int):
    """One element's feedback-code row [1, yt] -> its event bits as a
    [yt, lanes] tile, each clause row's bits across every lane.  Decoded
    on the row (one vreg an op) into

      P = t1∧cl, Q = t1∧¬cl, R = t2∧cl;
      +1 where the literal is 1: P;  +1 where it is 0: R (if excluded);
      −1 w.p. 1/s where the literal is 1: Q ∨ (P∧¬boost)
                                   (a boosted P keeps its +1);
      −1 w.p. 1/s where the literal is 0: P ∨ Q,

    packed one to a byte, then turned into clause rows by
    transposing the row broadcast over ``lanes`` sublanes.  ``keep`` is
    ¬boost as an int32 0/1 scalar."""
    cl = code_row & _CL
    t1 = (code_row >> 1) & 1
    p = t1 & cl
    q = t1 ^ p
    r = (code_row >> 2) & cl
    ev = p | ((q | (p & keep)) << 8) | (r << 16) | ((p | q) << 24)
    return jnp.broadcast_to(ev, (lanes, ev.shape[1])).T


def _tile_delta(low, litb, ev, acc):
    """One batch element's Alg-5 events on a TA block: ``low`` the cells
    whose random word is below p_ta (P = 1/s), ``litb`` the element's
    literals (a [1, xt] row, or one row per clause for the conv update),
    ``ev`` its :func:`_event_tile` rows.  Each cell adds the one or two
    bytes its literal and its draw select: four VPU ops."""
    sel = jnp.where(low, jnp.where(litb, 0x101, 0x1010000),
                    jnp.where(litb, 0x1, 0x10000))
    return acc + (ev & sel)


def _decode(acc, include):
    """Packed event counts -> the run's int32 delta: Type I +1s less both
    1/s −1s, plus the Type II +1s of excluded cells."""
    f = [jax.lax.shift_right_logical(acc, jnp.int32(8 * k)) & 0xFF
         for k in range(4)]
    return f[0] - f[1] - f[3] + jnp.where(include, 0, f[2])


def _batch_rows(lit_ref, b, r: int, S: int):
    """Row ``b`` of the literal block ([1, xt]), shared by every clause
    row — a ref-level dynamic sublane read, the form Mosaic lowers."""
    return lit_ref[pl.ds(b, 1), :]


def _conv_rows(words_ref, b, r: int, S: int, *, n_lit: int, xt: int):
    """Element ``b``'s literals for clause rows [r, r + S): [S, xt], one
    literal row per clause row (the patch that row is updated against).
    ``words_ref`` [n_lit, xt/32, yt] is word-major, so each word of the
    tile is one [1, yt] row turned into a clause column and spread over
    its 32 lanes; element ``b`` reads literal block ``b % n_lit`` (both
    feedback rounds of a datapoint share its patch choice)."""
    e = jax.lax.rem(b, n_lit)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, xt), 1)
    word, bit = lane >> 5, lane & 31
    lit = None
    for w in range(xt // 32):
        col = words_ref[e, pl.ds(w, 1), :][:, r:r + S].T      # [S, 1]
        lit = col if lit is None else jnp.where(word == w, col, lit)
    return (lit >> bit) & 1


def _low_mask(p_ta, prng: str, lfsr_bits: int, rand_bits: int):
    """``st -> word < p_ta`` for the stream family.  An LFSR at least
    rand_bits wide (and under 31) compares its raw lanes against p_ta
    shifted up to the lane width, as int32 — exact: the word is the
    lanes' top rand_bits, and p_ta is first capped at 2^rand_bits, past
    which every word is below it; the shift is built once per tile from
    the SMEM scalar.  A narrower LFSR (zero-extended words) and the
    counter chains emit the word."""
    sh = lfsr_bits - rand_bits
    if prng != "lfsr" or sh < 0 or lfsr_bits > 30:
        return lambda st: stream_word(st, prng, lfsr_bits, rand_bits) < p_ta
    thr = (jnp.minimum(p_ta, jnp.uint32(1 << rand_bits)) << sh).astype(
        jnp.int32)
    return lambda st: jax.lax.bitcast_convert_type(st[0], jnp.int32) < thr


def _tile_update(ci, li, ta_ref, lit_ref, code_ref, lmask_ref, params_ref,
                 out_ref, ev_ref, *, batch: int, n_l_tiles: int, yt: int,
                 xt: int, rand_bits: int, prng: str = "counter",
                 lfsr_bits: int = 24, seed_refresh: bool = True,
                 rows=_batch_rows, strip: int = _STRIP):
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
    the uint32 master seed crosses from SMEM.  The master–slave re-seed
    is compiled in only when the static batch reaches the 2^L−1 period
    (it cannot fire otherwise: every launch starts the cycle count at 0).

    ``ev_ref`` (VMEM scratch [min(batch, _RUN), yt, min(xt, 128)]) holds
    every element's :func:`_event_tile`.  They depend on the clause rows
    alone, so a batch of one run expands them on the first literal tile
    of each clause tile (grid axis 1 runs in order) and every later
    literal tile reuses them.  The batch loops then run per strip of
    ``strip`` rows (:data:`_STRIP`; the conv update :data:`_CONV_STRIP`).
    ``rows(lit_ref, b, r, S)`` reads batch element
    ``b``'s literals for rows [r, r + S): :func:`_batch_rows` (one
    literal row shared by every clause) or, for the conv update,
    :func:`_conv_rows`."""
    # dynamic model scalars ride in SMEM — a DTMProgram swap or a fresh
    # per-step seed never retraces (cache-size == 1 semantics, §IV-D-a).
    seed = params_ref[0, 0]
    p_ta = params_ref[0, 1]
    keep = (params_ref[0, 2] == 0).astype(jnp.int32)     # ¬boost
    n_states = params_ref[0, 3].astype(jnp.int32)
    row0 = params_ref[0, 4]
    refresh = seed_refresh and batch >= (1 << lfsr_bits) - 1
    low_of = _low_mask(p_ta, prng, lfsr_bits, rand_bits)
    lanes = ev_ref.shape[2]
    strips = [(r, min(strip, yt - r)) for r in range(0, yt, strip)]
    runs = [(lo, min(lo + _RUN, batch)) for lo in range(0, batch, _RUN)]

    # per-element stream keyed on GLOBAL element index — the result is
    # tile-layout independent (ref.py reproduces it exactly).
    keys, st, delta = [], [], []
    for r, S in strips:
        gy = (ci.astype(jnp.uint32) * yt + row0 + r
              + jax.lax.broadcasted_iota(jnp.uint32, (S, xt), 0))
        gx = (li.astype(jnp.uint32) * xt
              + jax.lax.broadcasted_iota(jnp.uint32, (S, xt), 1))
        keys.append(gy * jnp.uint32(n_l_tiles * xt) + gx)
        st.append(stream_start(seed, keys[-1], prng, lfsr_bits, refresh))
        delta.append(jnp.zeros((S, xt), jnp.int32))

    for lo, hi in runs:
        def expand(lo=lo, hi=hi):
            def put(b, carry):
                ev_ref[b - lo] = _event_tile(code_ref[pl.ds(b, 1), :], keep,
                                             lanes)
                return carry
            jax.lax.fori_loop(lo, hi, put, 0)

        if len(runs) == 1:
            pl.when(pl.program_id(1) == 0)(expand)
        else:
            expand()
        for i, (r, S) in enumerate(strips):
            def body(b, carry, lo=lo, r=r, S=S, key=keys[i]):
                st, acc = carry
                st = stream_step(st, key, prng, lfsr_bits)
                ev = ev_ref[b - lo, r:r + S, :]
                if lanes < xt:
                    ev = jnp.concatenate([ev] * (xt // lanes), 1)
                acc = _tile_delta(low_of(st), rows(lit_ref, b, r, S) > 0, ev,
                                  acc)
                return st, acc

            st[i], acc = jax.lax.fori_loop(
                lo, hi, body, (st[i], jnp.zeros((S, xt), jnp.int32)))
            include = ta_ref[r:r + S, :] >= (n_states >> 1)
            delta[i] = delta[i] + _decode(acc, include)

    lmask = lmask_ref[...]                                # Fig 6a inverse mask
    for (r, S), d in zip(strips, delta):
        out_ref[r:r + S, :] = jnp.clip(ta_ref[r:r + S, :] + d * lmask, 0,
                                       n_states - 1)


def _kernel(ta_ref, lit_ref, code_ref, lmask_ref, params_ref, out_ref,
            ev_ref, *, batch: int, n_l_tiles: int, yt: int, xt: int,
            rand_bits: int, prng: str, lfsr_bits: int, seed_refresh: bool):
    _tile_update(pl.program_id(0), pl.program_id(1), ta_ref, lit_ref,
                 code_ref, lmask_ref, params_ref, out_ref, ev_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh)


def _sparse_kernel(idx_ref, params_ref, ta_ref, lit_ref, code_ref,
                   lmask_ref, out_ref, ev_ref, *, batch: int,
                   n_l_tiles: int, yt: int, xt: int, rand_bits: int,
                   prng: str, lfsr_bits: int, seed_refresh: bool):
    """Compacted grid step: slot ``program_id(0)`` owns the ACTIVE clause
    tile whose original row-tile index is ``idx_ref[program_id(0)]`` (the
    scalar-prefetch index vector also drives the BlockSpec gathers).  The
    PRNG stream is keyed on the original tile coordinates, so the update
    is bit-identical to the dense kernel's for that tile."""
    _tile_update(idx_ref[pl.program_id(0)], pl.program_id(1), ta_ref,
                 lit_ref, code_ref, lmask_ref, params_ref, out_ref, ev_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh)


def _conv_kernel(ta_ref, words_ref, code_ref, lmask_ref, params_ref,
                 out_ref, ev_ref, *, batch: int, n_lit: int, n_l_tiles: int,
                 yt: int, xt: int, rand_bits: int, prng: str,
                 lfsr_bits: int, seed_refresh: bool):
    """Dense conv grid step: the flat tile body, with each clause row's
    literals read from its own chosen patch (:func:`_conv_rows`)."""
    _tile_update(pl.program_id(0), pl.program_id(1), ta_ref, words_ref,
                 code_ref, lmask_ref, params_ref, out_ref, ev_ref,
                 batch=batch, n_l_tiles=n_l_tiles, yt=yt, xt=xt,
                 rand_bits=rand_bits, prng=prng, lfsr_bits=lfsr_bits,
                 seed_refresh=seed_refresh,
                 rows=functools.partial(_conv_rows, n_lit=n_lit, xt=xt),
                 strip=_CONV_STRIP)


def _streamed_kernel(ta_ref, lit_ref, code_ref, lmask_ref, rand_ref,
                     params_ref, out_ref, *, batch: int, yt: int, xt: int):
    """Streamed-rand baseline: the same event counting, but the randoms
    arrive as a pre-materialised [B, yt, xt] uint32 block from HBM
    (ref.ta_rand_stream) — exactly the traffic the in-kernel generator
    eliminates.  Kept as a dispatchable path so the win is measurable on
    one machine (benchmarks/fig15_lfsr.py) and streamed-vs-in-kernel
    bit-identity is a test, not a claim."""
    p_ta = params_ref[0, 1]
    keep = (params_ref[0, 2] == 0).astype(jnp.int32)     # ¬boost
    n_states = params_ref[0, 3].astype(jnp.int32)
    ta = ta_ref[...]                                      # [yt, xt] int32
    delta = jnp.zeros((yt, xt), jnp.int32)
    for lo in range(0, batch, _RUN):
        def body(b, acc):
            ev = _event_tile(code_ref[pl.ds(b, 1), :], keep, xt)
            return _tile_delta(rand_ref[b] < p_ta, lit_ref[pl.ds(b, 1), :] > 0,
                               ev, acc)

        acc = jax.lax.fori_loop(lo, min(lo + _RUN, batch), body,
                                jnp.zeros((yt, xt), jnp.int32))
        delta = delta + _decode(acc, ta >= (n_states >> 1))
    delta = delta * lmask_ref[...]
    out_ref[...] = jnp.clip(ta + delta, 0, n_states - 1)


def _events_shape(B: int, yt: int, xt: int) -> tuple:
    """Shape of :func:`_tile_update`'s int32 event-tile scratch."""
    return (min(B, _RUN), yt, min(xt, 128))


def _vmem_need(B: int, yt: int, xt: int, rands: bool = False) -> int:
    """Per-step VMEM of the TA-update kernels: TA tile in + out, literal
    rows, feedback codes, l_mask, plus the event-tile scratch (+ the
    streamed rand block, which needs no scratch)."""
    blocks = [((yt, xt), 4), ((B, xt), 4), ((B, yt), 4), ((1, xt), 4),
              ((yt, xt), 4)]
    if rands:
        return block_bytes(*blocks, ((B, yt, xt), 4))
    return block_bytes(*blocks) + math.prod(_events_shape(B, yt, xt)) * 4


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
        scratch_shapes=[pltpu.VMEM(_events_shape(B, yt, xt), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_sparse_kernel, batch=B, n_l_tiles=grid[1], yt=yt,
                          xt=xt, rand_bits=rand_bits, prng=prng,
                          lfsr_bits=lfsr_bits, seed_refresh=seed_refresh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k * yt, L), jnp.int32),
        compiler_params=compiler_params(("parallel", "arbitrary"),
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
        scratch_shapes=[pltpu.VMEM(_events_shape(B, yt, xt), jnp.int32)],
        compiler_params=compiler_params(("parallel", "arbitrary"),
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
    need = (block_bytes(((yt, xt), 4), ((Bl, xt // 32, yt), 4),
                        ((E, yt), 4), ((1, xt), 4), ((yt, xt), 4))
            + math.prod(_events_shape(E, yt, xt)) * 4)
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
        scratch_shapes=[pltpu.VMEM(_events_shape(E, yt, xt), jnp.int32)],
        compiler_params=compiler_params(("parallel", "arbitrary"), need),
        interpret=interpret,
    )(ta.astype(jnp.int32), words, feedback_code(clause_out, type1, type2),
      l_mask.reshape(1, L).astype(jnp.int32), params)
