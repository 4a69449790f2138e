"""Multi-head latent attention (MLA, DeepSeek-V2) with a latent KV cache.

Per token, with ``R = kv_lora_rank`` and heads of ``dn`` (no rope) +
``dr`` (rope) query/key dims and ``dv`` value dims::

    [q_nope, q_pe]_h = (W_q x)_h                     (H × (dn + dr))
    [c, k_pe]        = W_kva x ;  c ← RMSNorm(c)     (R + dr)
    [k_nope, v]_h    = (W_kvb c)_h                   (H × (dn + dv))
    score_h(t, u)    = σ · [q_nope, rope(q_pe)]_h(t) · [k_nope, rope(k_pe)]_h(u)
    o                = W_o · concat_h(Σ_u softmax(score_h) v_h(u))

``k_pe`` is one rotary key shared by all heads; ``σ = (dn + dr)^-½ · m²``
with YaRN's temperature ``m`` (``common.yarn_mscale``).  Rope turns the
halves of each ``dr``-wide slice (``common.apply_rope``); the published
model turns interleaved pairs, which is a fixed permutation of ``W_q``'s
and ``W_kva``'s rope rows.

Training and prefill expand K and V per head.  The decode cache holds only
the latent rows, ``{"ckv": (L, B, C, R), "kpe": (L, B, C, dr)}`` (normed
``c`` and the rotated ``k_pe``), and decode attends over them with
``W_kvb`` absorbed: the key side folds ``W_kvb``'s key rows into the query,
``q̃_h = Σ_i q_nope_h,i · W_kvb[h, i, :]``, and the value side applies
``W_kvb``'s value rows to the attended latent.  Under PEQA,
``W_kvb = s ⊙ (Q − z)`` with each slot's own task scales, so the key side
is ``(q_nope ⊙ s_t) · (Q − z)`` and the value side is the codes' dot with
a per-row scale epilogue, as the task-stacked GEMV has it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import common, linear
from repro.models.attention import _cache_write


def init(rng, cfg: ModelConfig) -> dict:
    m, h, d = cfg.mla, cfg.n_heads, cfg.d_model
    ks = jax.random.split(rng, 4)
    return {
        "wq": linear.init(ks[0], d, h * m.qk_head_dim),
        "wkv_a": linear.init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_norm": common.norm_init(cfg, m.kv_lora_rank),
        "wkv_b": linear.init(ks[2], m.kv_lora_rank,
                             h * (m.qk_nope_head_dim + m.v_head_dim)),
        "wo": linear.init(ks[3], h * m.v_head_dim, d),
    }


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               n_layers: Optional[int] = None) -> dict:
    if cfg.kv_cache_dtype != "model":
        raise NotImplementedError("MLA keeps its latent cache in the model "
                                  "dtype (no int8 KV)")
    if cfg.swa_window is not None:
        raise NotImplementedError("MLA has no sliding-window ring cache")
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    dtype = jnp.dtype(cfg.dtype)
    lead = (n_layers, batch, seq_len)
    return {"ckv": jnp.zeros(lead + (cfg.mla.kv_lora_rank,), dtype),
            "kpe": jnp.zeros(lead + (cfg.mla.qk_rope_head_dim,), dtype)}


def softmax_scale(cfg: ModelConfig) -> float:
    s = cfg.mla.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if rs is not None:
        s *= common.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return s


def _project(p, x, cfg: ModelConfig, slots):
    """(q_nope (B,S,H,dn), q_pe (B,S,H,dr), c (B,S,R), k_pe (B,S,1,dr))."""
    m, h = cfg.mla, cfg.n_heads
    spec = cfg.quant.spec()
    b, s, _ = x.shape
    with jax.named_scope("attn.qkv"):
        q = linear.apply(p["wq"], x, spec,
                         slots=linear.slot_entry(slots, "wq"))
        q = q.reshape(b, s, h, m.qk_head_dim)
        kva = linear.apply(p["wkv_a"], x, spec,
                           slots=linear.slot_entry(slots, "wkv_a"))
        c = common.norm_apply(p["kv_norm"], kva[..., :m.kv_lora_rank], cfg)
        k_pe = kva[..., m.kv_lora_rank:][:, :, None]
        return (q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:],
                c, k_pe)


def _rope(q_pe, k_pe, pos, cfg: ModelConfig, per_slot: bool):
    with jax.named_scope("attn.rope"):
        freqs = common.rope_freqs(cfg, cfg.mla.qk_rope_head_dim)
        turn = common.apply_rope_slots if per_slot else common.apply_rope
        q_pe, k_pe = turn(q_pe, pos, freqs), turn(k_pe, pos, freqs)
        ms = common.rope_mscale(cfg)
        if ms != 1.0:
            q_pe, k_pe = q_pe * ms, k_pe * ms
        return q_pe, k_pe


def _out(p, o, cfg: ModelConfig, slots):
    with jax.named_scope("attn.out"):
        return linear.apply(p["wo"], o, cfg.quant.spec(),
                            slots=linear.slot_entry(slots, "wo"),
                            row_parallel=True)


def _expanded(p, x, cfg: ModelConfig, positions, slots):
    """Full-sequence causal attention with K/V expanded per head.
    Returns (out (B,S,d), c (B,S,R), rotated k_pe (B,S,dr))."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q_nope, q_pe, c, k_pe = _project(p, x, cfg, slots)
    q_pe, k_pe = _rope(q_pe, k_pe, positions, cfg, per_slot=False)
    with jax.named_scope("attn.kv_up"):
        kv = linear.apply(p["wkv_b"], c, cfg.quant.spec(),
                          slots=linear.slot_entry(slots, "wkv_b"))
        kv = kv.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :m.qk_nope_head_dim],
         jnp.broadcast_to(k_pe, (b, s, h, m.qk_rope_head_dim)).astype(
             kv.dtype)], -1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
    with jax.named_scope("attn.scores"):
        o = ops.attention(q, k, kv[..., m.qk_nope_head_dim:], causal=True,
                          scale=softmax_scale(cfg))
    o = _out(p, o.reshape(b, s, h * m.v_head_dim), cfg, slots)
    return o, c, k_pe[:, :, 0]


def apply_train(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: Optional[jax.Array] = None) -> jax.Array:
    pos = positions if positions is not None else jnp.arange(x.shape[1])
    return _expanded(p, x, cfg, pos, None)[0]


def apply_prefill(p: dict, x: jax.Array, cfg: ModelConfig, slots=None):
    """Prefill: (out (B,S,d), cache entry {ckv (B,S,R), kpe (B,S,dr)})."""
    o, c, k_pe = _expanded(p, x, cfg, jnp.arange(x.shape[1]), slots)
    dtype = jnp.dtype(cfg.dtype)
    return o, {"ckv": c.astype(dtype), "kpe": k_pe.astype(dtype)}


def _task_rows(stack, tids):
    """Rows ``tids`` of a (T, …) task stack, gathered straight from the
    layer stack when ``stack`` is a ``linear.LayerOf`` view."""
    if isinstance(stack, linear.LayerOf):
        return stack.stack[stack.index, tids]
    return stack[tids]


def _kvb_factors(p_kvb: dict, slots, cfg: ModelConfig, dtype):
    """``W_kvb`` as (Q (H, dn + dv, G, R/G), s, z (M, H, dn + dv, G)):
    ``W[h, i, g, j] = s[., h, i, g] · (Q[h, i, g, j] − z[., h, i, g])`` per
    row (a slot's task scales), or one set broadcast over the rows."""
    m, h = cfg.mla, cfg.n_heads
    r = m.kv_lora_rank
    width = m.qk_nope_head_dim + m.v_head_dim
    if "qw" not in p_kvb:
        w = p_kvb["w"].astype(dtype).reshape(h, width, 1, r)
        one = jnp.ones((1, h, width, 1), jnp.float32)
        return w, one, jnp.zeros_like(one)
    p_kvb = linear.plain_tree(p_kvb)    # dequantized here, not a kernel's
    ent = linear.slot_entry(slots, "wkv_b")
    if ent is None:
        s, z = p_kvb["scale"][None], p_kvb["zero"][None]
    else:
        tids, st = ent
        s, z = (_task_rows(st[key], tids) for key in ("scale", "zero"))
    g = s.shape[-1]
    unit = jnp.ones(s.shape[1:], jnp.float32)
    q = ops.dequantize_op(p_kvb["qw"], unit, jnp.zeros_like(unit), r,
                          cfg.quant.spec(), dtype)
    shape = (-1, h, width, g)
    return (q.reshape(h, width, g, r // g), s.astype(jnp.float32).reshape(
        shape), z.astype(jnp.float32).reshape(shape))


def apply_decode(p: dict, x: jax.Array, cfg: ModelConfig, cache: dict,
                 layer: jax.Array, pos: jax.Array, slots=None):
    """Decode step of S ≥ 1 tokens for layer ``layer`` of the latent pool.

    x (B, S, d); pos scalar or (B,) per slot.  The new tokens' latent rows
    are written in place into the carried pool first, then layer
    ``layer`` is read back.  Attention runs over the latent rows with
    ``W_kvb`` absorbed (module docstring).  Returns (out, new cache)."""
    m, h = cfg.mla, cfg.n_heads
    dn, dv = m.qk_nope_head_dim, m.v_head_dim
    b, s, _ = x.shape
    dtype = x.dtype
    q_nope, q_pe, c, k_pe = _project(p, x, cfg, slots)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q_pe, k_pe = _rope(q_pe, k_pe, pos_b, cfg, per_slot=True)
    rows = pos_b[:, None] + jnp.arange(s)
    cache = {"ckv": _cache_write(cache["ckv"], c, layer, rows),
             "kpe": _cache_write(cache["kpe"], k_pe[:, :, 0], layer, rows)}
    at = lambda name: jax.lax.dynamic_index_in_dim(cache[name], layer, 0,
                                                   keepdims=False)
    ckv, kpe = at("ckv"), at("kpe")                  # (B, C, R), (B, C, dr)
    with jax.named_scope("attn.latent"):
        qf, sc, zc = _kvb_factors(p["wkv_b"], slots, cfg, dtype)
        lead = (b, s) if sc.shape[0] == b * s else (1, 1)
        sc, zc = (t.reshape(lead + t.shape[1:]) for t in (sc, zc))
        # key side: q̃ = Σ_i (q_nope ⊙ s) (Q − z), per group of latent dims
        a = q_nope.astype(jnp.float32)[..., None] * sc[..., :dn, :]
        qt = jnp.einsum("bshig,higr->bshgr", a.astype(dtype), qf[:, :dn],
                        preferred_element_type=jnp.float32)
        qt = qt - jnp.einsum("bshig,bshig->bshg", a,
                             jnp.broadcast_to(zc[..., :dn, :], a.shape)
                             )[..., None]
        qt = qt.reshape(b, s, h, m.kv_lora_rank).astype(dtype)
        lg = (jnp.einsum("bshr,bcr->bhsc", qt, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshe,bce->bhsc", q_pe.astype(dtype), kpe,
                           preferred_element_type=jnp.float32))
        lg = lg * softmax_scale(cfg)
        seen = (jnp.arange(ckv.shape[1])[None, None, :]
                <= rows[:, :, None])                 # (B, S, C)
        lg = jnp.where(seen[:, None], lg, -jnp.inf)
        pr = jax.nn.softmax(lg, axis=-1)
        lat = jnp.einsum("bhsc,bcr->bhsr", pr.astype(dtype), ckv,
                         preferred_element_type=jnp.float32)
        lat = jnp.swapaxes(lat, 1, 2).astype(dtype)
        # value side: the codes' dot, then each row's scale epilogue
        g = qf.shape[2]
        lat_g = lat.reshape(b, s, h, g, -1)
        u = jnp.einsum("bshgr,higr->bshig", lat_g, qf[:, dn:],
                       preferred_element_type=jnp.float32)
        lsum = lat_g.astype(jnp.float32).sum(-1)[:, :, :, None, :]
        o = jnp.sum(sc[..., dn:, :] * (u - zc[..., dn:, :] * lsum), -1)
    o = _out(p, o.astype(dtype).reshape(b, s, h * dv), cfg, slots)
    return o, cache
