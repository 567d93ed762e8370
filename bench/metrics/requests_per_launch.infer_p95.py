"""Scheduler: inference requests answered per stacked launch."""
import readers


def read(ctx):
    return readers.requests_per_launch(ctx)
