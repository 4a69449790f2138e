"""Time per output token after the first, 90th percentile over requests
completed in the window (host clock)."""
from readers import tpot_ms


def read(ctx):
    return tpot_ms(ctx, 90)
