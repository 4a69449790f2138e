"""The yardstick's arithmetic: the chip's peaks and what a step needs.

Peaks come from ``peaks.json``, keyed by JAX's ``device_kind`` with their
source; a device kind missing there is an error.  A kernel's operations and
bytes for one call come from ``kernels/<kernel>.py``; a whole step's from
the functions below, counted from the configuration's shapes.  The least
time the chip could take is the larger of operations over the bf16 peak
and bytes over the memory bandwidth.

Counts of what the work needs, not of what the program does: training
counts the forward pass and the input gradient of every linear and of the
head (4·N per token; PEQA freezes the codes, and the scale gradient is
O(T·N·G) given the forward's per-group partial sums), and causal attention
forward and backward, with no recomputation.  Decode counts attention over
each slot's live positions, not over the pool's capacity.
"""
from __future__ import annotations

import json
from pathlib import Path

import named

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def kernel(name: str):
    """The cost module ``kernels/<name>.py`` of a kernel by its name."""
    return named.module("kernels", name)


def seconds(flops: float, nbytes: float, pk: dict) -> float:
    """Least time at the peaks."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])


def linears(cfg) -> list:
    """(name, out features N, in features K) of one layer's quantized
    linears."""
    hd = cfg.n_heads * cfg.d_head
    kvd = cfg.n_kv_heads * cfg.d_head
    out = [("wq", hd, cfg.d_model), ("wk", kvd, cfg.d_model),
           ("wv", kvd, cfg.d_model), ("wo", cfg.d_model, hd),
           ("up", cfg.d_ff, cfg.d_model), ("down", cfg.d_model, cfg.d_ff)]
    if cfg.act == "silu":
        out.append(("gate", cfg.d_ff, cfg.d_model))
    return out


def groups(cfg, k: int) -> int:
    g = cfg.quant.group_size
    return 1 if g is None else k // g


def kernel_calls(cfg, name: str, m: int, task_rows: int) -> list:
    """(flops, bytes) of each call of kernel ``name`` in one pass over all
    layers with ``m`` rows."""
    q = cfg.quant
    cost = kernel(name).cost
    return [cost(m, n, k, q.bits, q.layout, groups(cfg, k), task_rows)
            for _ in range(cfg.n_layers) for _, n, k in linears(cfg)]


def linear_params(cfg) -> int:
    return cfg.n_layers * sum(n * k for _, n, k in linears(cfg))


def _kv_bytes_per_token(cfg) -> int:
    return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.d_head * 2   # bf16 k, v


def decode_step(cfg, m: int, live_kv: int, task_rows: int) -> tuple:
    """(flops, bytes) one decode step needs: ``m`` slots, ``live_kv``
    positions attended in all, ``task_rows`` distinct tasks' scales."""
    calls = kernel_calls(cfg, "quant_gemv", m, task_rows)
    v, d = cfg.vocab_size, cfg.d_model
    attn = 4 * live_kv * cfg.n_heads * cfg.d_head * cfg.n_layers
    flops = sum(f for f, _ in calls) + 2 * m * v * d + attn
    nbytes = (sum(b for _, b in calls) + v * d * 4 + m * d * 4
              + live_kv * _kv_bytes_per_token(cfg))
    return flops, nbytes


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and input gradient of linears and head, causal attention
    forward and backward."""
    attn = 6 * seq * cfg.n_heads * cfg.d_head * cfg.n_layers
    return 4 * linear_params(cfg) + 4 * cfg.vocab_size * cfg.d_model + attn
