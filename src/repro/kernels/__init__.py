"""Pallas TPU kernels for the DTM compute hot-spots.

Layout per kernel: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
padded wrappers + batch-size-aware dispatch), ref.py (pure-jnp oracles,
bit-exact), autotune.py (measured TileConfig/path plan cache)."""
from .ops import (PATH_FUSED, PATH_MXU, PATH_PACKED, PATH_PACKED_MXU,
                  PATH_REF, TA_COMPACT, TA_DENSE, TA_PRNG_INKERNEL,
                  TA_PRNG_STREAM, clause_eval_op, class_sum_op,
                  fused_step_op, packed_clause_eval_op, packed_clause_mxu_op,
                  packed_step_op, resolve_interpret, resolve_skip,
                  resolve_ta_prng, round_select_op, select_path,
                  select_ta_path, ta_update_compact_op, ta_update_conv_op,
                  ta_update_op, tm_infer_op, unfused_step_op)
from . import autotune, ref

__all__ = ["clause_eval_op", "class_sum_op", "fused_step_op", "tm_infer_op",
           "packed_clause_eval_op", "packed_clause_mxu_op", "packed_step_op",
           "ta_update_op", "ta_update_compact_op", "ta_update_conv_op",
           "unfused_step_op",
           "round_select_op", "select_path", "select_ta_path",
           "resolve_interpret", "resolve_skip", "resolve_ta_prng",
           "PATH_MXU", "PATH_PACKED", "PATH_PACKED_MXU", "PATH_FUSED",
           "PATH_REF", "TA_DENSE", "TA_COMPACT", "TA_PRNG_INKERNEL",
           "TA_PRNG_STREAM", "autotune", "ref"]
