"""Median host time of ``Engine.admit`` (prefill, first-token sample and
slot write; it waits for the first token)."""
from readers import prefill_ms


def read(ctx):
    return prefill_ms(ctx, 50)
