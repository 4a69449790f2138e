"""Median host time of ``Engine.step`` (one decode step over the pool; it
waits for the sampled tokens)."""
from readers import decode_ms


def read(ctx):
    return decode_ms(ctx, 50)
