"""95th percentile of client latency from due time, ms (see readers.p95_ms)."""
import readers


def read(ctx):
    return readers.p95_ms(ctx)
