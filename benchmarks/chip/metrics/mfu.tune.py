"""Training FLOPs required per token (4·N of linears and head, causal
attention forward and backward, no recomputation) times tokens per second,
over the bf16 peak."""
from readers import mfu_tune as read  # noqa: F401
