"""Tokens generated in the window over the window (host clock)."""
from readers import out_tok_s as read  # noqa: F401
