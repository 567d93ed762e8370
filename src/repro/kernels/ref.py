"""Pure-jnp oracles for every Pallas kernel (bit-exact, integer domain).

Each function mirrors one kernel's contract exactly — including the
counter-based PRNG stream of ``ta_update`` — so tests assert *equality*,
not allclose: the whole DTM datapath is integer arithmetic (paper §IV-B).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# clause evaluation (clause_eval.py / packed_clause.py oracle)
# ---------------------------------------------------------------------------

def clause_eval_ref(literals: jax.Array, include: jax.Array,
                    eval_mode: bool = False) -> jax.Array:
    """literals [B, L] {0,1}, include [C, L] {0,1} -> clause [B, C] int32."""
    lit = literals.astype(bool)[:, None, :]
    inc = include.astype(bool)[None, :, :]
    fired = jnp.all(jnp.logical_or(~inc, lit), axis=-1)
    if eval_mode:
        fired &= include.astype(bool).any(axis=-1)[None, :]
    return fired.astype(jnp.int32)


def pack_bitplane(bits: jax.Array) -> jax.Array:
    """{0,1} [..., n] -> uint32 [..., ceil(n/32)], little-endian per word.

    Same layout as ``repro.core.booleanize.pack_literals`` (kept as a local
    definition so the kernels package stays import-independent of core;
    tests/test_packed_layout.py pins the two bit-for-bit)."""
    *lead, n = bits.shape
    pad = (-n) % 32
    b = jnp.pad(bits.astype(jnp.uint32), [(0, 0)] * len(lead) + [(0, pad)])
    b = b.reshape(*lead, -1, 32)
    w = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (b * w).sum(axis=-1).astype(jnp.uint32)


def pack_include(ta: jax.Array, n_states) -> jax.Array:
    """TA states [C, L] -> packed include bitplane uint32 [C, ceil(L/32)].

    The include action is ``ta >= n_states/2`` (paper §II-A-b); this is the
    bitplane the TA-update stage maintains incrementally so no consumer
    ever re-thresholds the full [C, L] TA matrix."""
    j = jnp.asarray(n_states, jnp.int32) >> 1
    return pack_bitplane(ta.astype(jnp.int32) >= j)


def tail_mask_words(packed: jax.Array, n_bits: int) -> jax.Array:
    """Zero all bits at positions >= n_bits in a packed [..., W] bitplane.

    Zero include words never veto a clause, so masking the *include* side
    is sufficient to make garbage tail bits (a ragged 2f not filling the
    last word) harmless in both firing and nonempty checks."""
    W = packed.shape[-1]
    assert 0 < n_bits <= 32 * W, (n_bits, W)
    pos = jnp.arange(W, dtype=jnp.uint32) * 32
    nb = jnp.uint32(n_bits)
    keep = jnp.clip(nb - jnp.minimum(pos, nb), 0, 32)       # bits kept/word
    full = jnp.uint32(0xFFFFFFFF)
    mask = jnp.where(keep >= 32, full,
                     (jnp.uint32(1) << keep) - jnp.uint32(1))
    return packed & mask


def packed_clause_eval_ref(packed_literals: jax.Array,
                           packed_include: jax.Array,
                           eval_mode: bool = False,
                           n_bits: int | None = None) -> jax.Array:
    """Same contract in the packed domain.  ``n_bits`` (the real literal
    count 2f) masks garbage tail bits in the last include word so they
    never veto a clause or fake a nonempty one."""
    if n_bits is not None:
        packed_include = tail_mask_words(packed_include, n_bits)
    lit = packed_literals[:, None, :]
    inc = packed_include[None, :, :]
    viol = jnp.bitwise_and(inc, jnp.bitwise_not(lit))
    fired = jnp.all(viol == 0, axis=-1)
    if eval_mode:
        fired &= (packed_include != 0).any(axis=-1)[None, :]
    return fired.astype(jnp.int32)


def unpack_bitplanes_i8(packed: jax.Array) -> jax.Array:
    """uint32 [..., W] -> int8 {0,1} [..., W*32] (little-endian per word —
    the inverse of :func:`pack_bitplane`, emitted at matmul dtype)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.astype(jnp.int8).reshape(*packed.shape[:-1], -1)


def packed_clause_mxu_ref(packed_literals: jax.Array,
                          packed_include: jax.Array,
                          eval_mode: bool = False,
                          n_bits: int | None = None) -> jax.Array:
    """Popcount-as-matmul oracle (kernels.packed_clause_eval_mxu): expand
    the packed words to int8 bitplanes and count violations as one int8
    dot product — ``viol[b, c] = Σ_l inc[c, l]·(1 − lit[b, l])``, fired
    iff viol == 0.  Bit-identical to :func:`packed_clause_eval_ref`; the
    matmul recast keeps the MXU busy at throughput batches where the
    word-serial VPU reduction is the bottleneck (the 65-nm all-popcount
    datapath argument, PAPERS.md arXiv 2501.19347)."""
    if n_bits is not None:
        packed_include = tail_mask_words(packed_include, n_bits)
    lit = unpack_bitplanes_i8(packed_literals)           # [B, W*32] {0,1}
    inc = unpack_bitplanes_i8(packed_include)            # [C, W*32] {0,1}
    viol = jax.lax.dot_general(
        (1 - lit), inc, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                # [B, C]
    fired = viol == 0
    if eval_mode:
        fired &= (packed_include != 0).any(axis=-1)[None, :]
    return fired.astype(jnp.int32)


# ---------------------------------------------------------------------------
# class sums (class_sum.py / tm_infer.py oracle)
# ---------------------------------------------------------------------------

def class_sum_ref(clauses: jax.Array, weights: jax.Array) -> jax.Array:
    """clauses [B, C], weights [H, C] -> [B, H] int32."""
    return jax.lax.dot_general(
        clauses.astype(jnp.int32), weights.astype(jnp.int32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def tm_infer_ref(literals: jax.Array, include: jax.Array, weights: jax.Array,
                 eval_mode: bool = True) -> jax.Array:
    cl = clause_eval_ref(literals, include, eval_mode)
    return class_sum_ref(cl, weights)


# ---------------------------------------------------------------------------
# fused training step (fused_step.py oracle)
# ---------------------------------------------------------------------------

NEG_INF_SUM = -(1 << 24)  # Fig 6d remainder pinning (= fused_step.NEG_INF_SUM)


def _round_select(sums, cls, y_c, rand, weights, cl_mask, T, w_frozen,
                  rand_bits):
    """Alg 3 integer-exact clause selection for one feedback round."""
    T = jnp.asarray(T, jnp.int32)
    csum = jnp.take_along_axis(sums, cls[:, None], axis=1)        # [B, 1]
    cs = jnp.clip(csum, -T, T)
    p_num = jnp.where(jnp.asarray(y_c) == 1, T - cs, T + cs)
    lhs = rand.astype(jnp.int32) * (2 * T)
    sel = lhs < (p_num << rand_bits)                              # [B, R]
    w_r = jnp.take(weights, cls, axis=0)                          # [B, R]
    elig = jnp.where(jnp.asarray(w_frozen, jnp.int32) > 0, w_r != 0, True)
    sel = sel & (cl_mask[None, :] > 0) & elig
    return sel.astype(jnp.int32)


def fused_step_ref(literals, include, weights, labels, neg_labels,
                   rand_lab, rand_neg, cl_mask, h_mask, T, w_frozen,
                   rand_bits: int = 16):
    """Oracle for kernels.fused_step — the unfused pipeline spelled out:
    clause_eval (training mode) → class_sum → Fig-6 masking → Alg-3
    feedback selection for the target and negated rounds.

    Clause eval uses the violation-matmul recast (bit-exact vs. the Eq-1
    AND-chain — test_properties.py) so this oracle also serves as the DTM
    engine's CPU fast path without materialising a [B, R, L] broadcast."""
    viol = jax.lax.dot_general(
        (1 - literals.astype(jnp.int32)), include.astype(jnp.int32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    clause = (viol == 0).astype(jnp.int32) * cl_mask[None, :].astype(jnp.int32)
    sums = class_sum_ref(clause, weights)
    sums = jnp.where(h_mask[None, :] > 0, sums, NEG_INF_SUM)
    sel_lab = _round_select(sums, labels, 1, rand_lab, weights, cl_mask,
                            T, w_frozen, rand_bits)
    sel_neg = _round_select(sums, neg_labels, 0, rand_neg, weights, cl_mask,
                            T, w_frozen, rand_bits)
    return clause, sums, sel_lab, sel_neg


def packed_step_ref(packed_literals, packed_include, weights, labels,
                    neg_labels, rand_lab, rand_neg, cl_mask, h_mask, T,
                    w_frozen, rand_bits: int = 16,
                    n_bits: int | None = None, mxu: bool = False):
    """Training-step front half on the bit-packed layout (edge batches).

    Bit-identical to :func:`fused_step_ref` on the corresponding dense
    inputs: packed clause eval (training mode — empty clauses fire, so no
    nonempty gate) → class sums → Fig-6 masking → Alg-3 selection.
    ``mxu=True`` swaps the clause-eval stage for the popcount-as-matmul
    recast (:func:`packed_clause_mxu_ref`) — identical outputs."""
    eval_fn = packed_clause_mxu_ref if mxu else packed_clause_eval_ref
    clause = eval_fn(packed_literals, packed_include,
                     eval_mode=False, n_bits=n_bits)
    clause = clause * cl_mask[None, :].astype(jnp.int32)
    sums = class_sum_ref(clause, weights)
    sums = jnp.where(h_mask[None, :] > 0, sums, NEG_INF_SUM)
    sel_lab = _round_select(sums, labels, 1, rand_lab, weights, cl_mask,
                            T, w_frozen, rand_bits)
    sel_neg = _round_select(sums, neg_labels, 0, rand_neg, weights, cl_mask,
                            T, w_frozen, rand_bits)
    return clause, sums, sel_lab, sel_neg


# ---------------------------------------------------------------------------
# TA update (ta_update.py oracle — reproduces the in-kernel PRNG stream)
# ---------------------------------------------------------------------------

def _splitmix32(x):
    x = (x + jnp.uint32(0x9E3779B9)).astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x21F0AAAD)
    x = (x ^ (x >> 15)) * jnp.uint32(0x735A2D97)
    return (x ^ (x >> 15)).astype(jnp.uint32)


def _xorshift32(x):
    x = x ^ (x << 13)
    x = x ^ (x >> 17)
    x = x ^ (x << 5)
    return x.astype(jnp.uint32)


# Maximal-length Galois LFSR tap masks — MUST mirror core.prng._TAPS
# bit-for-bit (tests/test_kernel_speed.py pins the two); kept as a local
# definition so the kernels package stays import-independent of core.
LFSR_TAPS = {
    4: 0b1100,
    8: 0b10111000,
    12: 0b111000001000,
    16: 0b1101000000001000,
    20: 0b10010000000000000000,
    24: 0b111000010000000000000000,
    32: 0b10000000001000000000000000000110,
}


def _lfsr_seed(master, key, lfsr_bits: int):
    """Per-element lane seed: splitmix of master ⊕ stream key, masked to
    the LFSR width, nonzero-forced (a Galois LFSR locks up at 0).  Same
    construction as core.prng._seed_lanes with lane index == key."""
    mask = jnp.uint32((1 << lfsr_bits) - 1)
    s = _splitmix32(jnp.asarray(master, jnp.uint32) ^ key) & mask
    return jnp.where(s == 0, jnp.uint32(1), s)


def _lfsr_advance(lanes, lfsr_bits: int):
    """One Galois LFSR shift per lane (== core.prng.lfsr_step)."""
    taps = jnp.uint32(LFSR_TAPS[lfsr_bits])
    return ((lanes >> 1) ^ ((lanes & jnp.uint32(1)) * taps)).astype(
        jnp.uint32)


def _lfsr_emit(lanes, lfsr_bits: int, rand_bits: int):
    """L-bit register -> rand_bits-wide comparator word (zero-extend when
    L < rand_bits — the Fig-15 quantisation — truncate high bits else)."""
    if lfsr_bits < rand_bits:
        out = lanes << (rand_bits - lfsr_bits)
    elif lfsr_bits > rand_bits:
        out = lanes >> (lfsr_bits - rand_bits)
    else:
        out = lanes
    return (out & jnp.uint32((1 << rand_bits) - 1)).astype(jnp.uint32)


def stream_keys(C: int, L: int, xt: int, row_idx=None):
    """Global per-element stream keys [C, L] uint32: row * stride + col,
    stride = L rounded up to whole xt tiles (what the padded kernel sees).
    ``row_idx`` [C] overrides the global row numbers (compaction/shards)."""
    stride = ((L + xt - 1) // xt) * xt
    if row_idx is None:
        gy = jax.lax.broadcasted_iota(jnp.uint32, (C, L), 0)
    else:
        gy = jnp.broadcast_to(row_idx.astype(jnp.uint32)[:, None], (C, L))
    gx = jax.lax.broadcasted_iota(jnp.uint32, (C, L), 1)
    return gy * jnp.uint32(stride) + gx


def stream_start(seed, key, prng: str, lfsr_bits: int,
                 seed_refresh: bool = True):
    """Initial per-element stream state (a tuple — ``prng`` is static).

    ``counter`` — splitmix32(seed ^ key) xorshift chains (the TPU-native
    counter mode).  ``lfsr`` — the paper's master–slave cluster with lane
    identity == key: lanes seeded splitmix32(seed ^ key) (masked, nonzero),
    plus the scalar (master, cycles) refresh state when ``seed_refresh``
    is set; without it the state is the lanes alone.  Pure elementwise
    jnp, shared verbatim by the Pallas TA-update kernels — generate where
    you consume, no random tensor in HBM."""
    seed = jnp.asarray(seed, jnp.uint32)
    if prng == "counter":
        return (_splitmix32(seed ^ key),)
    if prng != "lfsr":
        raise ValueError(f"unknown TA prng mode {prng!r}")
    lanes = _lfsr_seed(seed, key, lfsr_bits)
    return (lanes, seed, jnp.uint32(0)) if seed_refresh else (lanes,)


def stream_step(st, key, prng: str, lfsr_bits: int):
    """Advance the state one cycle (mirrors core.prng.cluster_next for the
    lfsr mode: shift every lane; a state that carries (master, cycles)
    xorshifts its master and re-seeds every lane when the 2^L−1 period
    elapses)."""
    if prng == "counter":
        return (_xorshift32(st[0]),)
    lanes = _lfsr_advance(st[0], lfsr_bits)
    if len(st) == 1:
        return (lanes,)
    _, master, cycles = st
    cycles = cycles + jnp.uint32(1)
    do = cycles >= jnp.uint32((1 << lfsr_bits) - 1)
    master = jnp.where(do, _xorshift32(master), master)
    lanes = jnp.where(do, _lfsr_seed(master, key, lfsr_bits), lanes)
    cycles = jnp.where(do, jnp.uint32(0), cycles)
    return (lanes, master, cycles)


def stream_word(st, prng: str, lfsr_bits: int, rand_bits: int):
    """The rand_bits-wide comparator word of the current state."""
    if prng == "counter":
        return st[0] >> (32 - rand_bits)
    return _lfsr_emit(st[0], lfsr_bits, rand_bits)


def stream_advance(st, key, prng: str, lfsr_bits: int, rand_bits: int):
    """Advance one cycle and emit its rand_bits-wide words."""
    st = stream_step(st, key, prng, lfsr_bits)
    return st, stream_word(st, prng, lfsr_bits, rand_bits)


def ta_rand_stream(seed, batch: int, C: int, L: int, rand_bits: int = 16,
                   prng: str = "counter", lfsr_bits: int = 24,
                   seed_refresh: bool = True, xt: int = 256, row_idx=None):
    """Materialise the TA-update random stream as a tensor [batch, C, L]
    uint32 — EXACTLY the numbers the in-kernel generator consumes in
    place.  This is the streamed baseline the in-kernel PRNG eliminates:
    batch·C·L·4 bytes of HBM random-bits traffic per step
    (benchmarks/fig15_lfsr.py measures the two against each other)."""
    key = stream_keys(C, L, xt, row_idx)
    st0 = stream_start(seed, key, prng, lfsr_bits, seed_refresh)

    def body(st, _):
        return stream_advance(st, key, prng, lfsr_bits, rand_bits)

    _, rows = jax.lax.scan(body, st0, None, length=batch)
    return rows


def _ta_delta_step(rand, lit_b, cl_b, t1_b, t2_b, include, p_ta, boost):
    """One batch element's Alg-5 TA delta [C, L] given its random words.
    ``lit_b`` is the element's literal row [L], or one row per clause
    [C, L] (the conv update: each clause's chosen patch)."""
    low = rand < jnp.asarray(p_ta, jnp.uint32)
    clb = (cl_b > 0)[:, None]
    litb = jnp.atleast_2d(lit_b > 0)
    cl_and_lit = clb & litb
    inc1 = jnp.where(boost, cl_and_lit, cl_and_lit & ~low)
    dec1 = ~cl_and_lit & low
    d1 = inc1.astype(jnp.int32) - dec1.astype(jnp.int32)
    inc2 = (clb & ~litb & ~include).astype(jnp.int32)
    return (jnp.where((t1_b > 0)[:, None], d1, 0)
            + jnp.where((t2_b > 0)[:, None], inc2, 0))


def ta_update_ref(ta, literals, clause_out, type1, type2, l_mask, seed,
                  p_ta, rand_bits=16, boost=True, n_states=256, xt=256,
                  row_idx=None, prng="counter", lfsr_bits=24,
                  seed_refresh=True, rands=None):
    """Bit-exact oracle for kernels.ta_update (same per-element streams).

    The stream is keyed on the element's global (row, col) index with the
    row stride rounded up to a whole number of ``xt``-wide tiles — exactly
    the stride the kernel sees after ops.ta_update_op pads L.  The oracle
    therefore matches the padded kernel bit-for-bit on ANY shape (padded
    columns have their own stream positions, but those never land in the
    [:C, :L] region), so CPU-ref and TPU-kernel training runs are
    reproducible against each other.

    ``row_idx`` (optional, [C] int) overrides each row's GLOBAL row number
    in the stream key — the clause-skip compaction path (ops.
    ta_update_compact_op) gathers only the active rows and passes their
    original indices here, so a compacted update reproduces the dense
    per-element streams exactly.

    ``prng`` selects the stream family: ``counter`` (splitmix/xorshift
    chains) or ``lfsr`` (the paper-faithful Galois master–slave cluster,
    ``lfsr_bits`` wide with optional ``seed_refresh`` — see
    :func:`stream_step`).  ``rands`` (optional, [B, C, L] uint32 from
    :func:`ta_rand_stream`) consumes pre-materialised randoms instead of
    generating in place — the streamed baseline path."""
    C, L = ta.shape
    boost = jnp.asarray(boost)
    n_states = jnp.asarray(n_states, jnp.int32)
    include = ta.astype(jnp.int32) >= (n_states >> 1)
    zero = jnp.zeros((C, L), jnp.int32)

    if rands is None:
        key = stream_keys(C, L, xt, row_idx)
        st0 = stream_start(seed, key, prng, lfsr_bits, seed_refresh)

        def body(carry, xs):
            st, delta = carry
            lit_b, cl_b, t1_b, t2_b = xs
            st, rand = stream_advance(st, key, prng, lfsr_bits, rand_bits)
            delta = delta + _ta_delta_step(rand, lit_b, cl_b, t1_b, t2_b,
                                           include, p_ta, boost)
            return (st, delta), None

        (_, delta), _ = jax.lax.scan(
            body, (st0, zero), (literals, clause_out, type1, type2))
    else:
        def body(delta, xs):
            lit_b, cl_b, t1_b, t2_b, rand = xs
            return delta + _ta_delta_step(rand, lit_b, cl_b, t1_b, t2_b,
                                          include, p_ta, boost), None

        delta, _ = jax.lax.scan(
            body, zero, (literals, clause_out, type1, type2, rands))
    delta = delta * l_mask.astype(jnp.int32)[None, :]
    return jnp.clip(ta.astype(jnp.int32) + delta, 0, n_states - 1)


def ta_update_conv_ref(ta, lit_words, clause_out, type1, type2, l_mask,
                       seed, p_ta, rand_bits=16, boost=True, n_states=256,
                       xt=256, row_idx=None, prng="counter", lfsr_bits=24,
                       seed_refresh=True):
    """Bit-exact oracle for kernels.ta_update_conv: :func:`ta_update_ref`
    with a literal row of its own for every (element, clause row).

    ``lit_words`` uint32 [Bl, C, W] holds, per element and clause row,
    the packed literals of the patch that row is updated against (W*32 >=
    L).  ``clause_out``/``type1``/``type2`` are [E, C] over the stacked
    feedback rounds (E = 2·Bl: every target round, then every negated
    round); element ``e`` reads ``lit_words[e % Bl]``, so both rounds of a
    datapoint use its one patch choice.  Streams, keys (``row_idx``,
    ``xt``) and families are exactly :func:`ta_update_ref`'s: a TA cell
    sees the same random numbers in a conv update as in a flat one."""
    C, L = ta.shape
    boost = jnp.asarray(boost)
    n_states = jnp.asarray(n_states, jnp.int32)
    include = ta.astype(jnp.int32) >= (n_states >> 1)
    key = stream_keys(C, L, xt, row_idx)
    st0 = stream_start(seed, key, prng, lfsr_bits, seed_refresh)
    reps = clause_out.shape[0] // lit_words.shape[0]
    words = jnp.concatenate([lit_words] * reps)                  # [E, C, W]

    def body(carry, xs):
        st, delta = carry
        w_b, cl_b, t1_b, t2_b = xs
        st, rand = stream_advance(st, key, prng, lfsr_bits, rand_bits)
        lit_b = unpack_bitplanes_i8(w_b)[:, :L]                  # [C, L]
        delta = delta + _ta_delta_step(rand, lit_b, cl_b, t1_b, t2_b,
                                       include, p_ta, boost)
        return (st, delta), None

    (_, delta), _ = jax.lax.scan(
        body, (st0, jnp.zeros((C, L), jnp.int32)),
        (words, clause_out, type1, type2))
    delta = delta * l_mask.astype(jnp.int32)[None, :]
    return jnp.clip(ta.astype(jnp.int32) + delta, 0, n_states - 1)
