"""Model step: inference operations a row needs x rows/s over the int8 peak, %."""
import kinds
import readers


def read(ctx):
    return readers.mfu(ctx, "infer", kinds.of(ctx["config"]).infer_ops_per_row)
