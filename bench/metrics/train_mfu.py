"""Model step: training operations a row needs x rows/s over the int8 peak, %."""
import kinds
import readers


def read(ctx):
    return readers.mfu(ctx, "train", kinds.of(ctx["config"]).train_ops_per_row)
