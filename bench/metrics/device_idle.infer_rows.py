"""Device: share of the traced window in which no operation ran, %."""
import readers


def read(ctx):
    return readers.idle(ctx)
