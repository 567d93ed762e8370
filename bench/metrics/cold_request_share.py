"""Server bank: share of requests served by the single-program cold path, %."""
import readers


def read(ctx):
    return readers.cold_share(ctx)
