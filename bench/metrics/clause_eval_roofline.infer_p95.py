"""Kernels: roofline time of the clause evaluation served over its kernels' device time, %."""
import readers


def read(ctx):
    return readers.clause_eval_roofline(ctx)
