"""Inference rows of the requests sent in the window over the time until the last was answered."""
import readers


def read(ctx):
    return readers.rate(ctx, "infer")
