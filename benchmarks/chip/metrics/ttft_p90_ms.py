"""Time to first token, 90th percentile over every request due in the
window, from its due time (host clock)."""
from readers import ttft_ms


def read(ctx):
    return ttft_ms(ctx, 90)
