"""Core DTM / Tsetlin Machine library (the paper's contribution)."""
from .types import (TMConfig, TileConfig, TMState, init_state, ta_actions,
                    VANILLA, COALESCED)
from .booleanize import (Booleanizer, fit_threshold, fit_thermometer,
                         to_literals, pack_literals)
from .clause import (clause_outputs_logical, clause_outputs_matmul,
                     class_sums, predict, vanilla_polarity)
from .prng import PRNG, LFSRState, make_cluster, lfsr_step, cluster_next
from .feedback import train_step, FeedbackStats
from .evaluate import (accuracy, batched_predict, epoch_record,
                       feedback_fit, fit_loop)
from .dtm import DTMEngine, DTMProgram, TMSession
from .tm_head import TMHead, pool_backbone_features
from . import regression_tm

__all__ = [
    "TMConfig", "TileConfig", "TMState", "init_state", "ta_actions",
    "VANILLA", "COALESCED", "Booleanizer", "fit_threshold", "fit_thermometer",
    "to_literals", "pack_literals", "clause_outputs_logical",
    "clause_outputs_matmul", "class_sums", "predict", "vanilla_polarity",
    "PRNG", "LFSRState", "make_cluster", "lfsr_step", "cluster_next",
    "train_step", "FeedbackStats", "DTMEngine", "TMSession",
    "regression_tm", "accuracy", "batched_predict",
    "epoch_record", "feedback_fit", "fit_loop",
    "DTMProgram", "TMHead", "pool_backbone_features",
]
