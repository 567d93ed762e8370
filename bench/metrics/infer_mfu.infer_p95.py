"""Model step: inference operations a row needs x rows/s over the int8 peak, %."""
import readers


def read(ctx):
    return readers.mfu(ctx, "infer", readers.work.infer_ops_per_row)
