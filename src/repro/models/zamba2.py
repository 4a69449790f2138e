"""Zamba2 hybrid: a Mamba2 backbone with ONE shared attention block applied
periodically (weight reuse across applications — Zamba's signature trick).

Layout: ``n_layers`` Mamba2 layers; after every ``attn_every`` of them the
shared transformer block runs, fed concat(h, e0) (current hidden + initial
embedding, width 2·d_model) as in Zamba2.  81 layers with attn_every=6 →
13 shared-block applications + 3 tail Mamba layers:

    [mamba ×6 → shared-attn] ×13 → [mamba ×3] → norm → head

Params are stacked (groups, attn_every, …) so the whole depth is two nested
lax.scans (HLO stays O(1) in depth).  Each application gets its own KV-cache
slot — (n_groups, B, C, Hkv, D) — but ONE set of weights.

Simplifications vs the released checkpoints (documented in DESIGN.md §6):
no per-application LoRA on the shared block; the shared block's MLP runs on
h (not on the concat); rotary attention instead of Zamba2's partial-rope.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import context as dctx
from repro.models import attention, common, linear, mamba2


def _layout(cfg: ModelConfig):
    every = cfg.attn_every or cfg.n_layers + 1
    n_groups = cfg.n_layers // every
    tail = cfg.n_layers - n_groups * every
    return every, n_groups, tail


def init(rng, cfg: ModelConfig) -> dict:
    every, n_groups, tail = _layout(cfg)
    ks = jax.random.split(rng, 6)

    def stack_init(r, n):
        return jax.vmap(lambda rr: mamba2.init(rr, cfg))(jax.random.split(r, n))

    grouped = stack_init(ks[0], n_groups * every)
    grouped = jax.tree.map(
        lambda l: l.reshape(n_groups, every, *l.shape[1:]), grouped)
    shared_ks = jax.random.split(ks[2], 2)
    params = {
        "embed": common.embed_init(ks[1], cfg),
        "mamba_groups": grouped,
        "shared": {
            "ln1": common.norm_init(cfg, 2 * cfg.d_model),
            "attn": attention.init(shared_ks[0], cfg, d_in=2 * cfg.d_model),
            "ln2": common.norm_init(cfg),
            "mlp": common.mlp_init(shared_ks[1], cfg),
        },
        "final_norm": common.norm_init(cfg),
    }
    if tail:
        params["mamba_tail"] = stack_init(ks[3], tail)
    params.update(common.head_init(ks[4], cfg))
    return params


def _shared_attn_train(shared: dict, h, e0, cfg: ModelConfig):
    a_in = jnp.concatenate([h, e0], axis=-1)
    a_in = common.norm_apply(shared["ln1"], a_in, cfg)
    h = h + attention.apply_train(shared["attn"], a_in, cfg)
    h = h + common.mlp_apply(shared["mlp"],
                             common.norm_apply(shared["ln2"], h, cfg), cfg)
    return dctx.constrain_tokens(h, cfg.seq_shard)


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig):
    every, n_groups, tail = _layout(cfg)
    h = common.embed_apply(params["embed"], tokens, cfg)
    e0 = h

    def group_body(h, group_p):
        def mamba_body(hh, layer_p):
            hh = hh + mamba2.apply_train(layer_p, hh, cfg)
            return dctx.constrain_tokens(hh, cfg.seq_shard), None
        body = mamba_body
        if cfg.remat in ("block", "full"):
            body = jax.checkpoint(mamba_body, prevent_cse=False)
        h, _ = jax.lax.scan(body, h, group_p)
        h = _shared_attn_train(params["shared"], h, e0, cfg)
        return h, None

    if cfg.remat in ("block", "full"):
        group_body = jax.checkpoint(group_body, prevent_cse=False)
    h, _ = jax.lax.scan(group_body, h, params["mamba_groups"])
    if tail:
        def tail_body(hh, layer_p):
            return hh + mamba2.apply_train(layer_p, hh, cfg), None
        h, _ = jax.lax.scan(tail_body, h, params["mamba_tail"])
    h = common.norm_apply(params["final_norm"], h, cfg)
    return common.head_apply(params, params["embed"], h, cfg)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch["tokens"], cfg)
    return common.cross_entropy(logits, batch["labels"], batch.get("mask"))


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    every, n_groups, tail = _layout(cfg)
    cap = attention.cache_capacity(cfg, seq_len)
    dtype = jnp.dtype(cfg.dtype)
    kv = (n_groups, batch, cap, cfg.n_kv_heads, cfg.d_head)
    st = mamba2.init_state(cfg, batch, n_layers=n_groups * every)
    cache = {
        "attn_k": jnp.zeros(kv, dtype),
        "attn_v": jnp.zeros(kv, dtype),
        "ssm": st["ssm"].reshape(n_groups, every, *st["ssm"].shape[1:]),
        "conv": st["conv"].reshape(n_groups, every, *st["conv"].shape[1:]),
    }
    if tail:
        t = mamba2.init_state(cfg, batch, n_layers=tail)
        cache["ssm_tail"], cache["conv_tail"] = t["ssm"], t["conv"]
    return cache


def prefill(params: dict, tokens: jax.Array, cfg: ModelConfig):
    """Prefill: returns (last_logits (B, V), cache)."""
    every, n_groups, tail = _layout(cfg)
    h = common.embed_apply(params["embed"], tokens, cfg)
    e0 = h
    b, s, _ = h.shape
    cap = attention.cache_capacity(cfg, s)
    shared = params["shared"]

    def group_body(h, group_p):
        def mamba_body(hh, layer_p):
            out, st = mamba2.apply_train(layer_p, hh, cfg, return_state=True)
            return hh + out, st
        h, states = jax.lax.scan(mamba_body, h, group_p)
        a_in = jnp.concatenate([h, e0], axis=-1)
        a_in = common.norm_apply(shared["ln1"], a_in, cfg)
        a, ck, cv = attention.apply_prefill(shared["attn"], a_in, cfg, cap)
        h = h + a
        h = h + common.mlp_apply(shared["mlp"],
                                 common.norm_apply(shared["ln2"], h, cfg), cfg)
        return h, (states, ck, cv)

    h, (states, ks_, vs_) = jax.lax.scan(group_body, h, params["mamba_groups"])
    cache = {"attn_k": ks_, "attn_v": vs_,
             "ssm": states["ssm"], "conv": states["conv"]}
    if tail:
        def tail_body(hh, layer_p):
            out, st = mamba2.apply_train(layer_p, hh, cfg, return_state=True)
            return hh + out, st
        h, tstates = jax.lax.scan(tail_body, h, params["mamba_tail"])
        cache["ssm_tail"], cache["conv_tail"] = tstates["ssm"], tstates["conv"]
    h = common.norm_apply(params["final_norm"], h, cfg)
    logits = common.head_apply(params, params["embed"], h[:, -1:], cfg)
    return logits[:, 0], cache


def decode_step(params: dict, cache: dict, tokens: jax.Array, pos: jax.Array,
                cfg: ModelConfig):
    every, n_groups, tail = _layout(cfg)
    h = common.embed_apply(params["embed"], tokens, cfg)
    e0 = h
    shared = params["shared"]

    def group_body(carry, xs):
        h, kv = carry
        group_p, ssm_g, conv_g, layer = xs

        def mamba_body(hh, inner):
            layer_p, s_l, c_l = inner
            out, s_l, c_l = mamba2.apply_decode(layer_p, hh, cfg, s_l, c_l)
            return hh + out, (s_l, c_l)

        h, (ssm_g, conv_g) = jax.lax.scan(mamba_body, h, (group_p, ssm_g, conv_g))
        a_in = jnp.concatenate([h, e0], axis=-1)
        a_in = common.norm_apply(shared["ln1"], a_in, cfg)
        a, kv = attention.apply_decode(shared["attn"], a_in, cfg, kv, layer,
                                       pos)
        h = h + a
        h = h + common.mlp_apply(shared["mlp"],
                                 common.norm_apply(shared["ln2"], h, cfg), cfg)
        return (h, kv), (ssm_g, conv_g)

    # the shared attention's pool rides in the carry
    # (transformer.decode_tokens); the small recurrent states stay xs/ys
    (h, kv), (ssm, conv) = jax.lax.scan(
        group_body, (h, {"k": cache["attn_k"], "v": cache["attn_v"]}),
        (params["mamba_groups"], cache["ssm"], cache["conv"],
         jnp.arange(cache["attn_k"].shape[0])))
    new_cache = dict(cache, ssm=ssm, conv=conv, attn_k=kv["k"],
                     attn_v=kv["v"])
    if tail:
        def tail_body(hh, inner):
            layer_p, s_l, c_l = inner
            out, s_l, c_l = mamba2.apply_decode(layer_p, hh, cfg, s_l, c_l)
            return hh + out, (s_l, c_l)
        h, (st, ct) = jax.lax.scan(
            tail_body, h,
            (params["mamba_tail"], cache["ssm_tail"], cache["conv_tail"]))
        new_cache["ssm_tail"], new_cache["conv_tail"] = st, ct
    h = common.norm_apply(params["final_norm"], h, cfg)
    logits = common.head_apply(params, params["embed"], h, cfg)
    return logits[:, 0], new_cache
