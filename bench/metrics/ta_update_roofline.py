"""Kernels: roofline time of the TA updates applied over their kernels' device time, %."""
import readers


def read(ctx):
    return readers.ta_update_roofline(ctx)
