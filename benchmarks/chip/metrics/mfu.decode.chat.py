"""Whole decode step: least time at the peaks (KV at live lengths) over the
measured step time, over every step of the window."""
from readers import mfu_decode as read  # noqa: F401
