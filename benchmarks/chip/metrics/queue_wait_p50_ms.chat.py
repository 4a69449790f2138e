"""Median wait from a request's due time to the start of its admission."""
from readers import queue_wait_ms


def read(ctx):
    return queue_wait_ms(ctx, 50)
