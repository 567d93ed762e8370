"""Distributed TM training (the scale path of the paper's algorithm).

Mapping onto the production mesh (DESIGN.md §4):
* batch sharded over ``data`` (and ``pod``) — each shard evaluates feedback
  for its datapoints against the replicated TM state;
* the integer TA/weight deltas are ``psum``'d across the data axes — the
  TM's "gradient all-reduce", natively integer.  Per-datapoint TA deltas
  are in {-1,0,+1} per round (two rounds), so for local batch ≤ 63 the
  wire format is EXACTLY int8 (4× smaller than f32 grads, zero loss);
* clause-axis sharding over ``model`` (huge-clause regime) is expressed by
  sharding ``state.ta`` rows — clause evaluation is local, only the [B, h]
  class sums psum over ``model``.

shard_map keeps the collectives explicit (the HLO the dry-run counts);
tests/test_distributed.py asserts dp == single-device batched mode exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import feedback
from .prng import LFSRState, PRNG, _seed_lanes
from .types import COALESCED, TMConfig, TMState, VANILLA


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off — the TM
    collectives are explicit integer psums/gathers into replicated
    outputs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def compact_rows_psum(d: jax.Array, axes, frac: float) -> jax.Array:
    """Alg-6 WIRE compaction of a row-sparse integer delta all-reduce.

    ``d`` [rows, ...] per-shard integer deltas; ``axes`` the mesh axis
    name(s) to reduce over; ``frac`` the static capacity fraction.  The
    shards first psum the (tiny, [rows] int32) active-row bitmap; when
    the UNION of active rows fits the capacity ``k = max(1, rows*frac)``,
    only those rows cross the wire (gather → psum → scatter), shrinking
    the dominant collective by ~1/frac at convergence (Fig 7: feedback
    falls to ≲25 % of clauses after the first epochs).  Overflow falls
    back to the dense psum — EXACT either way.  The branch predicate is
    derived from the psum'd bitmap, so every shard takes the same
    ``lax.cond`` branch (the collectives inside stay matched).

    ``frac <= 0`` (or a capacity that cannot beat dense) short-circuits
    to the plain dense psum."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def _dense(x):
        for a in axes:
            x = jax.lax.psum(x, a)
        return x

    rows = d.shape[0]
    k = max(1, int(rows * frac))
    if frac <= 0 or k >= rows:
        return _dense(d)
    nz = (d != 0).any(axis=tuple(range(1, d.ndim))).astype(jnp.int32)
    act = _dense(nz)
    # union size, not the summed per-shard counts — rows active on
    # several shards still occupy one compacted slot
    n_act = (act > 0).sum()

    def _compact(_):
        ridx = jnp.nonzero(act > 0, size=k, fill_value=rows - 1)[0]
        g = _dense(jnp.take(d, ridx, axis=0))
        return jnp.zeros_like(d).at[ridx].set(g)

    return jax.lax.cond(n_act <= k, _compact, lambda _: _dense(d), None)


def _shard_prng(cfg: TMConfig, seed: int, idx) -> PRNG:
    """Independent per-shard stream: master seed ⊕ shard index (the §IV-C
    master/slave reseeding pattern lifted to the mesh level)."""
    if cfg.prng_backend == "lfsr":
        n_lanes = max(1024, cfg.clauses * 2)
        base = jnp.uint32(seed) ^ (jnp.uint32(idx) + jnp.uint32(0x9E37))
        lanes = _seed_lanes(base, n_lanes, cfg.lfsr_bits)
        st = LFSRState(lanes=lanes, master=base, cycles=jnp.uint32(0))
        return PRNG("lfsr", cfg.lfsr_bits, cfg.rand_bits, cfg.seed_refresh,
                    st)
    if cfg.prng_backend == "counter":
        st = jnp.uint32(seed) ^ (jnp.uint32(idx) * jnp.uint32(0x85EBCA6B))
        return PRNG("counter", cfg.lfsr_bits, cfg.rand_bits,
                    cfg.seed_refresh, st)
    if cfg.prng_backend == "threefry":
        key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
        return PRNG("threefry", cfg.lfsr_bits, cfg.rand_bits,
                    cfg.seed_refresh, key)
    # TMConfig validates at construction; a hand-rolled cfg object (tests,
    # duck typing) must not silently get threefry streams on a typo.
    raise ValueError(
        f"prng_backend={cfg.prng_backend!r} not recognised; "
        "use lfsr, counter, or threefry")


def dp_train_step(cfg: TMConfig, state: TMState, literals: jax.Array,
                  labels: jax.Array, mesh, seed: int, chunk: int = 4,
                  int8_wire: bool = True, axis: str = "data",
                  compact_frac: float = 0.0):
    """Data-parallel batched TM step over one mesh axis.

    ``compact_frac`` > 0 enables Alg-6 WIRE compaction of the TA-delta
    all-reduce: the shards first psum the (tiny, [rows]) active-row
    bitmap; when the union of active rows fits the static capacity
    ``ceil(rows * compact_frac)``, only those rows cross the wire
    (gather → psum → scatter), shrinking the dominant collective by
    ~1/compact_frac at convergence (Fig 7: feedback falls to ≲25 % of
    clauses after the first epochs).  Falls back to the dense psum when
    the capacity overflows — EXACT either way.  The bucket predicate is
    derived from the psum'd bitmap, so every shard takes the same
    ``lax.cond`` branch (collectives inside the branches stay matched)."""
    nshards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    local_b = literals.shape[0] // nshards
    use_int8 = int8_wire and (2 * local_b) <= 127

    def shard_fn(ta, w, lit, lab):
        idx = jax.lax.axis_index(axis)
        prng = _shard_prng(cfg, seed, idx)
        st = TMState(ta, None if cfg.tm_type == VANILLA else w)
        _, d_ta, d_w, d_sel, corr = feedback.batched_deltas(
            cfg, st, prng, lit, lab, chunk)
        if use_int8:  # exact: |delta| <= 2·local_b <= 127
            d_ta = d_ta.astype(jnp.int8).astype(jnp.int32)
        d_ta = compact_rows_psum(d_ta, axis, compact_frac)
        d_w = jax.lax.psum(
            d_w if d_w is not None else jnp.zeros((1,), jnp.int32), axis)
        d_sel = jax.lax.psum(d_sel, axis)
        corr = jax.lax.psum(corr, axis)
        return d_ta, d_w, d_sel, corr

    w_arg = (state.weights if state.weights is not None
             else jnp.zeros((1,), jnp.int32))
    fn = shard_map(shard_fn, mesh, in_specs=(P(), P(), P(axis), P(axis)),
                   out_specs=(P(), P(), P(), P()))
    d_ta, d_w, d_sel, corr = fn(state.ta, w_arg, literals, labels)
    if cfg.tm_type == VANILLA:
        d_w = None
    return feedback.apply_deltas(cfg, state, d_ta, d_w, d_sel, corr)


# ---------------------------------------------------------------------------
# Pod-scale CoTM step: clause-sharding (model) × batch-sharding (data)
# ---------------------------------------------------------------------------

def pod_train_step(cfg: TMConfig, state: TMState, literals: jax.Array,
                   labels: jax.Array, mesh, seed: int,
                   compact_k: int = 0, compact_frac: float = 0.0):
    """Production-mesh CoTM training step (the paper's technique scaled to
    the 256/512-chip mesh — §Perf Cell C).

    Sharding: TA rows (clauses) over 'model' — the paper's y-dimension
    parallelism lifted to chips; batch over 'data' (and 'pod').  Exactly
    two collective families per step:
      · psum of partial class sums over 'model' (int32, [b, h] — tiny);
      · psum of integer TA/weight deltas over 'data'/'pod'.
    Everything else (clause eval, feedback, TA update) is shard-local,
    mirroring the FPGA's per-slice locality (Fig 5).

    ``compact_k`` > 0 enables FEEDBACK COMPACTION — the paper's Alg 6
    clause-skip realised as compute saving: per round, only the (at most)
    K selected clauses per shard get TA-delta math and random numbers
    (gather → update → scatter-add).  EXACT whenever #selected ≤ K per
    round (tested); Fig 7 shows feedback falls to ≲25 % of clauses after
    the first epochs, so K = c_loc/4 loses nothing at convergence while
    cutting the dominant elementwise+PRNG FLOPs by c_loc/K.

    ``compact_frac`` > 0 additionally WIRE-compacts the cross-data-shard
    TA-delta psum through :func:`compact_rows_psum` (the same Alg-6 unit
    applied to the collective instead of the compute): only the union of
    active clause rows crosses the 'data'/'pod' links, with the exact
    dense psum as the overflow fallback."""
    assert cfg.tm_type == COALESCED
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    dp = tuple(axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = 1
    for a in dp:
        n_data *= sizes[a]
    B_loc = literals.shape[0] // n_data
    c_loc = cfg.clauses // sizes["model"]
    J = cfg.include_threshold

    def shard_fn(ta, w, lit, lab):
        # ta [c_loc, 2f]; w [h, c_loc]; lit [B_loc, 2f]; lab [B_loc]
        didx = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index(dp[0]) * sizes[dp[1]]
            + jax.lax.axis_index(dp[1]))
        midx = jax.lax.axis_index("model")
        include = (ta >= J)
        inc_i = include.astype(jnp.int32)
        viol = jax.lax.dot_general(
            (1 - lit.astype(jnp.int32)), inc_i,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        cl = (viol == 0).astype(jnp.int32)                 # [B_loc, c_loc]
        part = jax.lax.dot_general(
            cl, w, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)              # [B_loc, h]
        sums = jax.lax.psum(part, "model")                 # global class sums
        correct = (jnp.argmax(sums, -1) == lab).sum()

        # class-choice rand must AGREE across model shards of one datapoint
        c_prng = _shard_prng(cfg, seed, didx)
        c_prng, c_rand = c_prng.bits((B_loc,))
        # clause/TA rands are per (data, model) shard — fully local streams
        l_prng = _shard_prng(cfg, seed + 1,
                             didx * sizes["model"] + midx + 17)

        def per_point(carry, xs):
            prng, acc_ta, acc_w, acc_sel = carry
            lit_1, lab_1, cl_1, sums_1, cr = xs
            prng, sel_rand = prng.bits((2, c_loc))
            prng, round_keys = prng.bits((2,))  # seeds the indexed streams
            from .prng import indexed_bits
            neg = feedback.negated_class(cfg.classes, lab_1, cr)
            for r, (cls, y_c) in enumerate(((lab_1, 1), (neg, 0))):
                csum = jnp.take(sums_1, cls)
                w_row = jnp.take(w, cls, axis=0)
                if compact_k <= 0:
                    ta_rand = indexed_bits(
                        round_keys[r], jnp.arange(c_loc, dtype=jnp.uint32),
                        cfg.literals, cfg.rand_bits)
                    d_ta, d_w, sel = feedback.round_deltas(
                        cfg, include, lit_1, cl_1, w_row, csum,
                        jnp.asarray(y_c), sel_rand[r], ta_rand)
                    acc_ta = acc_ta + d_ta
                else:
                    # Alg-6 compaction (shared unit — feedback.py): gather
                    # the ≤K selected clause rows, update only those,
                    # scatter-add back.  Clause-indexed randoms keep this
                    # BIT-EXACT vs the dense path whenever #selected ≤ K
                    # (tested).
                    sel = feedback.select_clauses(
                        cfg, csum, jnp.asarray(y_c), sel_rand[r])
                    d_ta_k, idx, d_w = feedback.compact_round_deltas(
                        cfg, include, lit_1, cl_1, w_row, csum,
                        jnp.asarray(y_c), sel, round_keys[r], compact_k)
                    acc_ta = acc_ta.at[idx].add(d_ta_k)
                acc_w = acc_w.at[cls].add(d_w)
                acc_sel = acc_sel + sel
            return (prng, acc_ta, acc_w, acc_sel), None

        z = (l_prng,
             jnp.zeros((c_loc, cfg.literals), jnp.int32),
             jnp.zeros((cfg.classes, c_loc), jnp.int32),
             jnp.zeros((c_loc,), jnp.int32))
        (_, d_ta, d_w, d_sel), _ = jax.lax.scan(
            per_point, z, (lit, lab, cl, sums, c_rand))
        # integer delta reduction across the batch shards (int8-exact wire
        # when 2·B_loc ≤ 127 — DESIGN.md §2.7); the dominant [c_loc, 2f]
        # TA-delta collective optionally rides the Alg-6 wire compaction
        d_ta = compact_rows_psum(d_ta, dp, compact_frac)
        for a in dp:
            d_w = jax.lax.psum(d_w, a)
            d_sel = jax.lax.psum(d_sel, a)
            correct = jax.lax.psum(correct, a)
        return d_ta, d_w, d_sel, correct

    dp_spec = dp if len(dp) > 1 else dp[0]
    fn = shard_map(
        shard_fn, mesh,
        in_specs=(P("model", None), P(None, "model"), P(dp_spec, None),
                  P(dp_spec)),
        out_specs=(P("model", None), P(None, "model"), P("model"), P()))
    d_ta, d_w, d_sel, corr = fn(state.ta, state.weights, literals, labels)
    new_ta = feedback.apply_ta_delta(cfg, state.ta, d_ta)
    new_w = feedback.apply_w_delta(cfg, state.weights, d_w)
    return TMState(new_ta, new_w), {"selected": d_sel.sum(),
                                    "correct": corr}
