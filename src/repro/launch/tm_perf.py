"""Analytic TM-datapath performance model (TPU v5e roofline terms).

Analytic op counts over the peaks of the device in use
(``mesh.hardware_model``; CPU runs rehearse with the v5e row) — model
figures, never measurements.  Centralised
in launch/ (next to the LM flops model) so the per-figure benchmark modules
don't each carry their own copy.

``train_front_costs`` models the training-step front half (clause eval ->
class sums -> Alg-3 feedback selection) in its two implementations:

* unfused — the seed three-stage path: the ``[B, C]`` int32 clause matrix
  is written to HBM by clause_eval, read back by class_sum, and the class
  sums are re-read by the jnp selection pass;
* fused   — one launch: the clause tile feeds the class-sum matmul in
  VMEM; the clause matrix is written once (the TA-update kernel consumes
  it) and the selection masks are emitted in-kernel.

The delta is pure HBM traffic — the quantity the FPGA design eliminates by
construction and the fused kernel eliminates on TPU.
"""
from __future__ import annotations

from .mesh import hardware_model


def roofline_s(flops: float, bytes_: float) -> float:
    """Seconds at the device's roofline (``mesh.hardware_model``; the
    v5e row on CPU): max(compute term, HBM term)."""
    hw = hardware_model()
    return max(flops / hw.peak_flops_bf16, bytes_ / hw.hbm_bw)


def train_front_costs(B: int, L: int, C: int, H: int) -> dict:
    """Analytic op/byte counts for the training-step front half.

    B datapoints, L literals, C clause rows, H classes.  Literals/include
    are int8, everything else int32."""
    # ops: violation matmul + class-sum matmul + two selection compares
    flops = 2 * B * C * L + 2 * B * C * H + 6 * B * C
    lit = B * L                       # int8
    inc = C * L                       # int8
    w = H * C * 4
    clause = B * C * 4
    sums = B * H * 4
    sel_io = 2 * B * C * 4            # two rounds of randoms in
    sel_out = 2 * B * C * 4           # two selection masks out
    shared = lit + inc + w + sums + sel_io + sel_out
    # unfused: clause written + read back, sums written + re-read by select
    unfused_bytes = shared + 2 * clause + sums
    # fused: clause written once (TA-update consumer), nothing re-read
    fused_bytes = shared + clause
    return {
        "flops": flops,
        "unfused_bytes": unfused_bytes,
        "fused_bytes": fused_bytes,
        "unfused_roofline_s": roofline_s(flops, unfused_bytes),
        "fused_roofline_s": roofline_s(flops, fused_bytes),
    }


def program_bytes(L: int, C: int, H: int, ta_bits: int = 8) -> int:
    """RAM image of one lowered DTMProgram at PADDED geometry (L literals,
    C clause rows, H classes) — the quantity the pod planner compares
    against the per-device VMEM budget: uint8 TA plane [C, L] (int32 iff
    ta_bits > 8), packed include bitplane [C, ceil(L/32)] uint32, weight
    matrix [H, C] int32, plus the int32 row/column/class masks."""
    W = (L + 31) // 32
    ta = C * L * (1 if ta_bits <= 8 else 4)
    inc = C * W * 4
    weights = H * C * 4
    masks = (C + L + H) * 4
    return ta + inc + weights + masks


def clause_shard_step_s(B: int, L: int, C: int, H: int,
                        shards: int) -> dict:
    """Roofline estimate of one clause-sharded train step: each shard
    runs the :func:`train_front_costs` fused datapath on its C/shards row
    window, then the [B, H] int32 class sums cross the ICI once
    (ring all-reduce moves ``2·(s-1)/s`` of the buffer per chip)."""
    local = train_front_costs(B, L, max(C // shards, 1), H)
    psum_bytes = (0 if shards <= 1
                  else 2 * (shards - 1) / shards * B * H * 4)
    ici_s = psum_bytes / hardware_model().collective_bw()
    return {
        "local_s": local["fused_roofline_s"],
        "psum_bytes": psum_bytes,
        "ici_s": ici_s,
        "step_s": local["fused_roofline_s"] + ici_s,
    }


def packed_eval_costs(B: int, L: int, C: int) -> dict:
    """Roofline terms for one packed clause-eval call on its two legs
    (kernels.packed_clause; the autotune seed plan reads this).

    Both legs stream the same packed bytes (W = ceil(L/32) uint32 words
    per row) and write the same [B, C] int32 clause matrix; they differ
    only in the compute engine:

    * vpu — one AND+NOT+OR word op per (b, c, w) triple on the 8×128
      vector unit;
    * mxu — int8 bitplane dot products, 2·B·C·L int8 ops on the systolic
      array, derated by batch occupancy (a B-tall operand fills at most
      min(B, 128) of the 128 MXU rows).

    The crossover is pure arithmetic-engine throughput: at B=1 the MXU
    runs ~1/128 occupied and the VPU wins; by B≳32 the matmul recast is
    far ahead.  Returned seconds are v5e figures — autotune's measure
    mode replaces them with wall-clock on the actual device."""
    hw = hardware_model()
    W = (L + 31) // 32
    io = clause_eval_bytes(B, L, C, packed=True)["total_bytes"]
    # VPU: one uint32 word op per (b, c, w) at the VPU word rate
    vpu_word_ops = B * C * W
    vpu_s = max(vpu_word_ops / hw.vpu_word_ops, io / hw.hbm_bw)
    # MXU: int8 peak, scaled by row occupancy
    mxu_ops = 2 * B * C * (W * 32)
    occupancy = min(B, 128) / 128
    mxu_s = max(mxu_ops / (hw.peak_int8_ops * max(occupancy, 1e-9)),
                io / hw.hbm_bw)
    return {
        "bytes": io,
        "vpu_word_ops": vpu_word_ops,
        "mxu_int8_ops": mxu_ops,
        "vpu_s": vpu_s,
        "mxu_s": mxu_s,
        "winner": "mxu_popcount" if mxu_s < vpu_s else "packed_vpu",
    }


def ta_rand_bytes(B: int, L: int, C: int) -> dict:
    """HBM random-bits traffic of one TA-update step, streamed vs
    in-kernel (the §IV-C frugality argument benchmarks/fig15_lfsr.py
    guards): the streamed baseline materialises one uint32 word per
    (batch, clause, literal) cell; the in-kernel generator moves only the
    master seed (one SMEM scalar)."""
    streamed = B * C * L * 4
    return {"streamed_rand_bytes": streamed, "inkernel_rand_bytes": 0,
            "streamed_rand_s": streamed / hardware_model().hbm_bw}


def clause_eval_bytes(B: int, L: int, C: int, packed: bool) -> dict:
    """Bytes moved by one clause-evaluation call (the edge-regime hot
    loop's memory bill — paper Fig 4-6's frugal-BRAM argument).

    Unpacked: int8 literals [B, L] + int8 include [C, L].
    Packed:   uint32 words, 32 literals each — [B, W] + [C, W],
    W = ceil(L/32): exactly 8× fewer literal bytes and 8× fewer include
    bytes than the int8 dense pair (32× vs the int32 include the engine
    used to re-threshold per call).  Output [B, C] int32 is identical.
    """
    W = (L + 31) // 32
    lit = B * W * 4 if packed else B * L
    inc = C * W * 4 if packed else C * L
    out = B * C * 4
    return {"literal_bytes": lit, "include_bytes": inc, "out_bytes": out,
            "total_bytes": lit + inc + out}
