"""Static Pallas kernel contract checker (dtmlint part 2).

For every registered kernel in ``repro/kernels/`` this module rebuilds
the launch geometry — grid, BlockSpec shapes, index maps, scratch — as
declarative plans and verifies, WITHOUT running anything:

* **bounds**: no grid step maps a block past the padded operand bounds
  (no out-of-bounds tiles);
* **coverage**: the output index maps tile every output block exactly
  (remainder rows exist only as caller-side padding, which the ops
  wrappers add and strip — the checker verifies padded dims divide);
* **tiling**: Mosaic's block rule — the last two block dims are
  multiples of (8, 128) or span the whole array dim;
* **VMEM**: the per-grid-step footprint — every HBM-streamed block
  double-buffered, plus VMEM scratch — fits the budget the launch gets:
  Mosaic's scoped default, or the explicit ``vmem_limit_bytes`` the
  kernel requests for a larger plan (``kernels.tpu_params``) — for EVERY
  tile plan the autotuner can emit (``EVAL_TILES``/``TRAIN_TILES``/
  ``TA_TILES`` × the plan-key grid of shapes and batch buckets).  No plan
  the tuner can persist may be unlaunchable (the eFPGA runtime-tunable
  TM work, arXiv 2502.07823, does the same budget validation pre-load).

Index maps are the REAL lambdas from the kernel modules' contracts,
restated here; they are affine coordinate projections, so the checker
probes them with unit grid vectors and verifies linearity instead of
enumerating the full grid product.  The AOT compile in
``tests/test_tpu_compile.py`` is the ground truth these rules mirror.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Tuple

from repro.kernels.autotune import EVAL_TILES, TA_TILES, TRAIN_TILES
from repro.kernels.class_sum import N_LIMBS
from repro.kernels.ops import _skip_caps
from repro.kernels.tpu_params import SCOPED_VMEM_BYTES, vmem_limit

__all__ = ["KernelPlan", "Violation", "build_plans", "check_plan",
           "check_all", "main"]

_WORD = 32      # packed literals: uint32 words


@dataclasses.dataclass(frozen=True)
class BlockUse:
    """One operand of a pallas_call: padded dims, block, index map."""
    name: str
    dims: Tuple[int, ...]               # padded array shape
    block: Tuple[int, ...]              # BlockSpec block shape
    index_map: Callable[..., Tuple[int, ...]]
    elem_bytes: int = 4
    smem: bool = False                  # scalar block: no double buffer
    gather_axes: Tuple[int, ...] = ()   # axes fed by a prefetched index
    is_output: bool = False


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    kernel: str
    desc: str                           # e.g. "eval/b256/L1024xR512 wt=128"
    grid: Tuple[int, ...]
    uses: Tuple[BlockUse, ...]
    scratch_bytes: int = 0


@dataclasses.dataclass(frozen=True)
class Violation:
    kernel: str
    desc: str
    kind: str                           # oob|coverage|divide|tiling|vmem
    detail: str

    def render(self) -> str:
        return f"{self.kernel} [{self.desc}] {self.kind}: {self.detail}"


# --------------------------------------------------------------------------- #
# geometry helpers (the ops-wrapper padding contract)                         #
# --------------------------------------------------------------------------- #

def _pad_to(n: int, t: int) -> int:
    return -(-n // t) * t


def _packed_tile(L: int, wt: int) -> Tuple[int, int]:
    """(padded words, word tile) as ``ops.lane_tile`` + padding give
    them: a tile at least as wide as the row becomes the whole row."""
    W = -(-L // _WORD)
    wt = W if W <= wt else wt
    return _pad_to(W, wt), wt


# --------------------------------------------------------------------------- #
# kernel plan builders — one per pallas_call in repro/kernels/                #
# --------------------------------------------------------------------------- #

def plan_clause_eval(B, L, C, bt=8, yt=128, xt=256) -> KernelPlan:
    B, C, L = _pad_to(B, bt), _pad_to(C, yt), _pad_to(L, xt)
    grid = (B // bt, C // yt, L // xt)
    return KernelPlan(
        "clause_eval", f"B{B} L{L} C{C} bt{bt} yt{yt} xt{xt}", grid,
        (BlockUse("neg_lit", (B, L), (bt, xt), lambda b, c, k: (b, k), 1),
         BlockUse("include", (C, L), (yt, xt), lambda b, c, k: (c, k), 1),
         BlockUse("clause", (B, C), (bt, yt), lambda b, c, k: (b, c), 4,
                  is_output=True)),
        scratch_bytes=(bt * yt + yt) * 4)


def plan_packed_clause(B, L, C, bt=8, yt=128, wt=128,
                       kernel="packed_clause_eval") -> KernelPlan:
    B, C = _pad_to(B, bt), _pad_to(C, yt)
    W, wt = _packed_tile(L, wt)
    grid = (B // bt, C // yt, W // wt)
    return KernelPlan(
        kernel, f"B{B} W{W} C{C} bt{bt} yt{yt} wt{wt}", grid,
        (BlockUse("plits", (B, W), (bt, wt), lambda b, c, k: (b, k), 4),
         BlockUse("pinc", (C, W), (yt, wt), lambda b, c, k: (c, k), 4),
         BlockUse("clause", (B, C), (bt, yt), lambda b, c, k: (b, c), 4,
                  is_output=True)),
        scratch_bytes=(bt * yt + yt) * 4)


def plan_class_sum(B, C, H, bt=8, mt=128) -> KernelPlan:
    B, C = _pad_to(B, bt), _pad_to(C, mt)
    grid = (B // bt, C // mt)
    return KernelPlan(
        "class_sum", f"B{B} C{C} H{H} bt{bt} mt{mt}", grid,
        (BlockUse("clauses", (B, C), (bt, mt), lambda b, k: (b, k), 1),
         BlockUse("weight_limbs", (N_LIMBS, H, C), (N_LIMBS, H, mt),
                  lambda b, k: (0, 0, k), 1),
         BlockUse("sums", (B, H), (bt, H), lambda b, k: (b, 0), 4,
                  is_output=True)),
        scratch_bytes=bt * H * 4)


def plan_tm_infer(B, L, C, H, bt=8, yt=128, xt=256) -> KernelPlan:
    B, C, L = _pad_to(B, bt), _pad_to(C, yt), _pad_to(L, xt)
    grid = (B // bt, C // yt, L // xt)
    return KernelPlan(
        "tm_infer", f"B{B} L{L} C{C} H{H} bt{bt} yt{yt} xt{xt}", grid,
        (BlockUse("neg_lit", (B, L), (bt, xt), lambda b, c, k: (b, k), 1),
         BlockUse("include", (C, L), (yt, xt), lambda b, c, k: (c, k), 1),
         BlockUse("weight_limbs", (N_LIMBS, H, C), (N_LIMBS, H, yt),
                  lambda b, c, k: (0, 0, c), 1),
         BlockUse("sums", (B, H), (bt, H), lambda b, c, k: (b, 0), 4,
                  is_output=True)),
        scratch_bytes=(bt * yt + yt + bt * H) * 4)


def plan_fused_step(B, L, R, H, bt=8, yt=128, xt=256) -> KernelPlan:
    B, R, L = _pad_to(B, bt), _pad_to(R, yt), _pad_to(L, xt)
    grid = (B // bt, R // yt, L // xt)
    bh = lambda b, c, k: (b, 0)         # noqa: E731 — map shorthand
    return KernelPlan(
        "fused_step", f"B{B} L{L} R{R} H{H} bt{bt} yt{yt} xt{xt}", grid,
        (BlockUse("neg_lit", (B, L), (bt, xt), lambda b, c, k: (b, k), 1),
         BlockUse("include", (R, L), (yt, xt), lambda b, c, k: (c, k), 1),
         BlockUse("weight_limbs", (N_LIMBS, H, R), (N_LIMBS, H, yt),
                  lambda b, c, k: (0, 0, c), 1),
         BlockUse("lab_oh", (B, H), (bt, H), bh, 4),
         BlockUse("neg_oh", (B, H), (bt, H), bh, 4),
         BlockUse("w_lab", (B, R), (bt, R), bh, 4),
         BlockUse("w_neg", (B, R), (bt, R), bh, 4),
         BlockUse("rand_lab", (B, R), (bt, R), bh, 4),
         BlockUse("rand_neg", (B, R), (bt, R), bh, 4),
         BlockUse("cl_mask_t", (1, R), (1, yt), lambda b, c, k: (0, c), 4),
         BlockUse("cl_mask", (1, R), (1, R), lambda b, c, k: (0, 0), 4),
         BlockUse("h_mask", (1, H), (1, H), lambda b, c, k: (0, 0), 4),
         BlockUse("params", (1, 2), (1, 2), lambda b, c, k: (0, 0), 4,
                  smem=True),
         BlockUse("clause", (B, R), (bt, yt), lambda b, c, k: (b, c), 4,
                  is_output=True),
         BlockUse("sums", (B, H), (bt, H), bh, 4, is_output=True),
         BlockUse("sel_lab", (B, R), (bt, R), bh, 4, is_output=True),
         BlockUse("sel_neg", (B, R), (bt, R), bh, 4, is_output=True)),
        scratch_bytes=(bt * yt + bt * H) * 4)


def plan_ta_update(B, L, C, yt=128, xt=256) -> KernelPlan:
    C, L = _pad_to(C, yt), _pad_to(L, xt)
    grid = (C // yt, L // xt)
    return KernelPlan(
        "ta_update", f"B{B} L{L} C{C} yt{yt} xt{xt}", grid,
        (BlockUse("ta", (C, L), (yt, xt), lambda c, l: (c, l), 4),
         BlockUse("literals", (B, L), (B, xt), lambda c, l: (0, l), 4),
         BlockUse("fb_code", (B, C), (B, yt), lambda c, l: (0, c), 4),
         BlockUse("l_mask", (1, L), (1, xt), lambda c, l: (0, l), 4),
         BlockUse("params", (1, 5), (1, 5), lambda c, l: (0, 0), 4,
                  smem=True),
         BlockUse("ta_out", (C, L), (yt, xt), lambda c, l: (c, l), 4,
                  is_output=True)))


def plan_ta_update_sparse(B, L, C, k, yt=128, xt=256) -> KernelPlan:
    C, L = _pad_to(C, yt), _pad_to(L, xt)
    grid = (k, L // xt)
    # tile_idx values are < C//yt; gathered axes are bounds-checked at
    # the max index, coverage is by construction (compacted output).
    g = C // yt - 1
    return KernelPlan(
        "ta_update_sparse", f"B{B} L{L} C{C} k{k} yt{yt} xt{xt}", grid,
        (BlockUse("ta", (C, L), (yt, xt), lambda c, l: (g, l), 4,
                  gather_axes=(0,)),
         BlockUse("literals", (B, L), (B, xt), lambda c, l: (0, l), 4),
         BlockUse("fb_code", (B, C), (B, yt), lambda c, l: (0, g), 4,
                  gather_axes=(1,)),
         BlockUse("l_mask", (1, L), (1, xt), lambda c, l: (0, l), 4),
         BlockUse("ta_out", (k * yt, L), (yt, xt), lambda c, l: (c, l), 4,
                  is_output=True)))


def plan_ta_update_streamed(B, L, C, yt=128, xt=256) -> KernelPlan:
    base = plan_ta_update(B, L, C, yt, xt)
    C_p, L_p = _pad_to(C, yt), _pad_to(L, xt)
    rands = BlockUse("rands", (B, C_p, L_p), (B, yt, xt),
                     lambda c, l: (0, c, l), 4)
    return dataclasses.replace(
        base, kernel="ta_update_streamed",
        uses=base.uses[:-1] + (rands, base.uses[-1]))


def plan_ta_update_conv(B, L, C, yt=128, xt=256) -> KernelPlan:
    """The conv TA update: ``B`` datapoints' chosen-patch literal words,
    word-major ``[B, L/32, C]``, and both feedback rounds' codes
    ``[2B, C]``."""
    C, L = _pad_to(C, yt), _pad_to(L, xt)
    grid = (C // yt, L // xt)
    return KernelPlan(
        "ta_update_conv", f"B{B} L{L} C{C} yt{yt} xt{xt}", grid,
        (BlockUse("ta", (C, L), (yt, xt), lambda c, l: (c, l), 4),
         BlockUse("lit_words", (B, L // _WORD, C), (B, xt // _WORD, yt),
                  lambda c, l: (0, l, c), 4),
         BlockUse("fb_code", (2 * B, C), (2 * B, yt), lambda c, l: (0, c),
                  4),
         BlockUse("l_mask", (1, L), (1, xt), lambda c, l: (0, l), 4),
         BlockUse("params", (1, 5), (1, 5), lambda c, l: (0, 0), 4,
                  smem=True),
         BlockUse("ta_out", (C, L), (yt, xt), lambda c, l: (c, l), 4,
                  is_output=True)))


# --------------------------------------------------------------------------- #
# checks                                                                      #
# --------------------------------------------------------------------------- #

def _affine(index_map, grid) -> Optional[List[Tuple[int, ...]]]:
    """Probe an index map with unit grid vectors; return per-grid-axis
    coefficient tuples, or None if the map is not affine (checker then
    falls back to full enumeration)."""
    g = len(grid)
    zero = tuple(index_map(*([0] * g)))
    coefs = []
    for j in range(g):
        probe = [0] * g
        probe[j] = 1
        v = tuple(index_map(*probe))
        coefs.append(tuple(vi - zi for vi, zi in zip(v, zero)))
    corner = [max(0, n - 1) for n in grid]
    want = tuple(z + sum(c[a] * corner[j] for j, c in enumerate(coefs))
                 for a, z in enumerate(zero))
    if tuple(index_map(*corner)) != want:
        return None
    return [zero] + coefs               # [base, coef_axis0, ...]


def check_plan(plan: KernelPlan,
               vmem_bytes: Optional[float] = None) -> List[Violation]:
    """Every violation of one plan.  ``vmem_bytes`` overrides the VMEM
    budget (default: what the launch gets — the scoped default, or the
    kernel's explicit request for a larger footprint)."""
    out: List[Violation] = []

    def bad(kind, detail):
        out.append(Violation(plan.kernel, plan.desc, kind, detail))

    vmem = plan.scratch_bytes
    for u in plan.uses:
        # --- divide: padded dims must tile exactly --------------------
        for a, (d, b) in enumerate(zip(u.dims, u.block)):
            if d % b:
                bad("divide", f"{u.name} axis {a}: dim {d} % block {b}")
        # --- tiling: Mosaic's (8, 128)-or-whole-dim block rule ----------
        if not u.smem and len(u.block) >= 2:
            for a, q in ((-2, 8), (-1, 128)):
                d, b = u.dims[a], u.block[a]
                if b % q and b != d:
                    bad("tiling", f"{u.name} axis {len(u.dims) + a}: block "
                                  f"{b} is neither a multiple of {q} nor "
                                  f"the whole dim {d}")
        lin = _affine(u.index_map, plan.grid)
        if lin is None:
            bad("oob", f"{u.name}: non-affine index map")
            continue
        base, coefs = lin[0], lin[1:]
        nblocks = tuple(d // b for d, b in zip(u.dims, u.block))
        # --- bounds: max block index within padded dims ---------------
        hi = tuple(z + sum(c[a] * max(0, plan.grid[j] - 1)
                           for j, c in enumerate(coefs))
                   for a, z in enumerate(base))
        for a in range(len(u.dims)):
            if a in u.gather_axes:
                continue                # builder already probed max idx
            if hi[a] >= nblocks[a] or base[a] < 0:
                bad("oob", f"{u.name} axis {a}: block index reaches "
                           f"{hi[a]} of {nblocks[a]}")
        # --- coverage: outputs must tile the array exactly ------------
        if u.is_output:
            for a in range(len(u.dims)):
                if a in u.gather_axes:
                    continue
                feeders = [j for j, c in enumerate(coefs) if c[a]]
                img = {base[a]}
                if feeders:
                    j = feeders[0]
                    if len(feeders) > 1 or coefs[j][a] != 1:
                        bad("coverage",
                            f"{u.name} axis {a}: non-unit index map")
                        continue
                    img = {base[a] + i for i in range(plan.grid[j])}
                if img != set(range(nblocks[a])):
                    bad("coverage",
                        f"{u.name} axis {a}: grid writes blocks "
                        f"{sorted(img)[:4]}.. of {nblocks[a]}")
        # --- VMEM: double-buffer everything HBM-streamed --------------
        blk = math.prod(u.block) * u.elem_bytes
        vmem += blk if u.smem else 2 * blk
    if vmem_bytes is None:
        try:
            vmem_bytes = vmem_limit(vmem) or SCOPED_VMEM_BYTES
        except ValueError as e:
            bad("vmem", str(e))
            return out
    if vmem > vmem_bytes:
        bad("vmem", f"per-step footprint {vmem / 1e6:.1f} MB exceeds "
                    f"the {vmem_bytes / 1e6:.1f} MB VMEM budget")
    return out


# --------------------------------------------------------------------------- #
# the audit space: every plan the tuner can emit                              #
# --------------------------------------------------------------------------- #

# the Conv CoTM at the CTM's MNIST geometry (bench/configs/mnist-convtm.json)
CONV_MNIST_SHAPE = (384, 2048, 16)


def _audit_shapes() -> List[Tuple[int, int, int]]:
    """(L, R, H) plan-key shapes: the benchmark sweep grid plus the
    committed TileConfig geometries (padded, as the engine pads them)."""
    shapes = {(1024, 512, 8), (256, 128, 4)}     # autotune_bench GRID
    from repro.configs.tm_paper import DTM_L_TILE, DTM_S_TILE
    for tile in (DTM_L_TILE, DTM_S_TILE):
        shapes.add(tuple(tile.padded_dims()))
    return sorted(shapes | {CONV_MNIST_SHAPE})


# batch buckets the plan key can hold: edge regime through the largest
# bench bucket (plan keys bucket to powers of two).
AUDIT_BATCHES = (1, 4, 8, 32, 256, 1024)
# the streamed TA baseline only launches at fig15's edge batches — its
# [B, C, L] uint32 rand stream is the thing the in-kernel PRNG deletes.
STREAMED_BATCHES = (1, 8)


def build_plans() -> List[KernelPlan]:
    plans: List[KernelPlan] = []
    for L, R, H in _audit_shapes():
        for B in AUDIT_BATCHES:
            for t in EVAL_TILES:        # eval stage: packed VPU + MXU legs
                plans.append(plan_packed_clause(B, L, R, **t))
                plans.append(plan_packed_clause(
                    B, L, R, kernel="packed_clause_eval_mxu", **t))
            for t in TRAIN_TILES:       # train stage: fused + unfused mxu
                plans.append(plan_fused_step(B, L, R, H, **t))
                plans.append(plan_clause_eval(B, L, R, bt=t["bt"],
                                              yt=t["yt"], xt=t["xt"]))
                plans.append(plan_class_sum(B, R, H, bt=t["bt"]))
            plans.append(plan_tm_infer(B, L, R, H))
            for t in TA_TILES:          # ta stage: dense + every skip cap
                plans.append(plan_ta_update(B, L, R, **t))
                n_groups = _pad_to(R, t["yt"]) // t["yt"]
                for k in (*_skip_caps(n_groups), n_groups):
                    plans.append(plan_ta_update_sparse(B, L, R, k, **t))
            # the conv update launches at the engine's default tile
            plans.append(plan_ta_update_conv(B, L, R))
        for B in STREAMED_BATCHES:
            for t in TA_TILES:
                plans.append(plan_ta_update_streamed(B, L, R, **t))
    return plans


def check_all(vmem_bytes: Optional[float] = None
              ) -> Tuple[int, List[Violation]]:
    plans = build_plans()
    violations: List[Violation] = []
    for p in plans:
        violations.extend(check_plan(p, vmem_bytes))
    return len(plans), violations


def main(argv: Sequence[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="dtmlint kernels", description=__doc__.splitlines()[0])
    ap.add_argument("--vmem-bytes", type=float, default=None,
                    help="override the VMEM budget (default: the launch's "
                         "scoped or explicitly requested limit)")
    ns = ap.parse_args(list(argv))
    n, violations = check_all(ns.vmem_bytes)
    for v in violations:
        print(v.render())
    print(f"kernel contract: {n} plans audited, "
          f"{len(violations)} violation(s)")
    return 1 if violations else 0
