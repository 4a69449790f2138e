"""What a decode step of a MoE model with latent attention needs, for the
least time at the peaks (``roofline.seconds``).

``roofline.decode_step`` counts a dense model's linears (``roofline
.linears``) and per-head K/V; a MoE step reads only the experts its rows
touch, and MLA attends over one latent row a position.  Counted here, for
one decode step of ``m`` slots: the attention linears of every layer
(``W_kvb`` absorbed into the query and the output reads its codes once,
as a GEMV of ``m`` rows would), the leading dense blocks' MLP, the router
and the shared experts of every MoE layer, the routed experts hit (the
step's ``experts_hit`` counter, summed over MoE layers, each read once
with the scale rows of the tasks used), the float32 head, and the latent
KV at live lengths (``R + dr`` values a position and layer; scores and
values over the latent).  Counts of what the work needs, not of what the
program does: the tiles' padding rows and untouched experts are left out.
"""
from __future__ import annotations

import roofline


def attn_linears(cfg) -> list:
    m, h, d = cfg.mla, cfg.n_heads, cfg.d_model
    return [(h * m.qk_head_dim, d), (m.kv_lora_rank + m.qk_rope_head_dim, d),
            (h * (m.qk_nope_head_dim + m.v_head_dim), m.kv_lora_rank),
            (d, h * m.v_head_dim)]


def gated(width: int, d: int) -> list:
    return [(width, d), (width, d), (d, width)]


def decode_step(cfg, m: int, live_kv: int, task_rows: float,
                experts_hit: float) -> tuple:
    """(flops, bytes) one decode step needs: ``m`` slots, ``live_kv``
    positions attended in all, ``task_rows`` distinct tasks,
    ``experts_hit`` experts with a row summed over the MoE layers."""
    q, mc, d = cfg.quant, cfg.moe, cfg.d_model
    gemv = roofline.kernel("quant_gemv").cost
    gmm = roofline.kernel("quant_gmm").cost
    dense = cfg.n_dense_layers
    moe = cfg.n_layers - dense
    lin = lambda n, k: gemv(m, n, k, q.bits, q.layout,
                            roofline.groups(cfg, k), task_rows)
    calls = [lin(n, k) for _ in range(cfg.n_layers)
             for n, k in attn_linears(cfg)]
    calls += [lin(n, k) for _ in range(dense) for n, k in gated(cfg.d_ff, d)]
    f = mc.d_ff_expert or cfg.d_ff
    calls += [lin(n, k) for _ in range(moe)
              for n, k in gated(f * mc.n_shared_experts, d)
              if mc.n_shared_experts]
    hit = experts_hit / max(moe, 1)                 # mean a layer
    calls += [gmm(m * mc.top_k, n, k, q.bits, q.layout,
                  roofline.groups(cfg, k), task_rows, hit)
              for _ in range(moe) for n, k in gated(f, d)]
    la = cfg.mla
    lat = la.kv_lora_rank + la.qk_rope_head_dim
    v = cfg.vocab_size
    flops = (sum(c[0] for c in calls)
             + moe * 2 * m * mc.n_experts * d                       # router
             + 2 * live_kv * cfg.n_heads * (lat + la.kv_lora_rank)
             * cfg.n_layers
             + 2 * m * v * d)
    nbytes = (sum(c[1] for c in calls)
              + moe * mc.n_experts * d * 4
              + live_kv * cfg.n_layers * lat * 2
              + v * d * 4 + m * d * 4)
    return flops, nbytes
