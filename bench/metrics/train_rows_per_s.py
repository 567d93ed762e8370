"""Training rows of the whole epochs run in the window over the time they took."""
import readers


def read(ctx):
    return readers.rate(ctx, "train")
