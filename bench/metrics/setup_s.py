"""Process start to the first timed request, compilation included."""
import readers


def read(ctx):
    return ctx["setup_s"]
