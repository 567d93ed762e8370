"""Model step: training operations a row needs x rows/s over the int8 peak, %."""
import readers


def read(ctx):
    return readers.mfu(ctx, "train", readers.work.train_ops_per_row)
