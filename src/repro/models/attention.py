"""GQA attention with RoPE, sliding-window, KV caches and cross-attention.

Cache layout (stacked over layers): {"k": (L, B, C, Hkv, D), "v": ...} where
C = cache capacity.  Dense-attention archs use C = seq_len and write slot
``pos``; SWA archs use C = window and write slot ``pos % window`` (a ring
buffer — the visible set is then exactly the last `window` tokens, so the
mask "slot ≤ pos" is correct in both regimes; see ref.flash_attention_ref).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import linear
from repro.models.common import apply_rope, apply_rope_slots, rope_freqs


def init(rng, cfg: ModelConfig, d_in: Optional[int] = None) -> dict:
    d_in = d_in or cfg.d_model
    dh = cfg.d_head
    ks = jax.random.split(rng, 4)
    return {
        "wq": linear.init(ks[0], d_in, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": linear.init(ks[1], d_in, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wv": linear.init(ks[2], d_in, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wo": linear.init(ks[3], cfg.n_heads * dh, cfg.d_model),
    }


def cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.swa_window is not None:
        return min(cfg.swa_window, seq_len)
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               n_layers: Optional[int] = None) -> dict:
    """Stacked-over-layers self-attention cache.

    kv_cache_dtype='int8' (§Perf): values stored int8 with one f16 scale per
    (token, head) — halves the decode memory-roofline term; the dequant
    fuses into the attention dot (the paper §3.2 notes PEQA composes with
    weight-activation quantization — this is that composition for the KV)."""
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    c = cache_capacity(cfg, seq_len)
    shape = (n_layers, batch, c, cfg.n_kv_heads, cfg.d_head)
    if cfg.kv_cache_dtype == "int8":
        sshape = (n_layers, batch, c, cfg.n_kv_heads)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float16),
                "v_scale": jnp.zeros(sshape, jnp.float16)}
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _qkv(p, x, cfg: ModelConfig, slots=None):
    with jax.named_scope("attn.qkv"):
        spec = cfg.quant.spec()
        b, s, _ = x.shape
        dh = cfg.d_head
        ent = lambda name: linear.slot_entry(slots, name)
        q = linear.apply(p["wq"], x, spec,
                         slots=ent("wq")).reshape(b, s, cfg.n_heads, dh)
        k = linear.apply(p["wk"], x, spec,
                         slots=ent("wk")).reshape(b, s, cfg.n_kv_heads, dh)
        v = linear.apply(p["wv"], x, spec,
                         slots=ent("wv")).reshape(b, s, cfg.n_kv_heads, dh)
        return q, k, v


def _rope_full(q, k, positions, cfg: ModelConfig):
    with jax.named_scope("attn.rope"):
        freqs = rope_freqs(cfg)
        return (apply_rope(q, positions, freqs),
                apply_rope(k, positions, freqs))


def _scores(q, k, v, cfg: ModelConfig, **kw):
    """Attention over the keys (``ops.attention``), heads merged."""
    with jax.named_scope("attn.scores"):
        b, s = q.shape[:2]
        o = ops.attention(q, k, v, causal=True, **kw)
        return o.reshape(b, s, cfg.n_heads * cfg.d_head)


def _out(p, o, cfg: ModelConfig, slots=None):
    with jax.named_scope("attn.out"):
        return linear.apply(p["wo"], o, cfg.quant.spec(),
                            slots=linear.slot_entry(slots, "wo"),
                            row_parallel=True)


def apply_train(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence causal attention (training / prefill)."""
    s = x.shape[1]
    q, k, v = _qkv(p, x, cfg)
    if cfg.use_rope:
        pos = positions if positions is not None else jnp.arange(s)
        q, k = _rope_full(q, k, pos, cfg)
    o = _scores(q, k, v, cfg, window=cfg.swa_window, impl=cfg.attn_impl)
    return _out(p, o, cfg)


def _rope_decode(q, k, pos, cfg: ModelConfig):
    """RoPE for a decode step of S ≥ 1 tokens starting at ``pos``; pos scalar
    or (B,) per-slot (token s of row b is at absolute position pos[b] + s)."""
    with jax.named_scope("attn.rope"):
        freqs = rope_freqs(cfg)
        if jnp.ndim(pos) == 1:
            return (apply_rope_slots(q, pos, freqs),
                    apply_rope_slots(k, pos, freqs))
        pos = pos + jnp.arange(q.shape[1])
        return apply_rope(q, pos, freqs), apply_rope(k, pos, freqs)


def _cache_write(buf, val, layer, rows):
    """Write the new tokens' K/V (or scale) rows into layer ``layer`` of the
    stacked cache ``buf`` (L, B, C, ...); val (B, S, ...), rows (B, S) the
    cache row of each token.

    One scatter of the S·B new rows: inside the decode layer scan ``buf`` is
    the carried (donated) pool, so XLA updates it in place.  The batch dim
    is a scatter batching dim (each batch row writes only its own rows), so
    a batch-sharded cache stays shard-local, and the pool keeps its layout.
    A row past the capacity is dropped, not clamped onto the last rows.
    """
    with jax.named_scope("attn.kv_write"):
        idx = jnp.stack([jnp.broadcast_to(layer, rows.shape), rows], axis=-1)
        dims = jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(2, val.ndim)),
            inserted_window_dims=(0, 2),
            scatter_dims_to_operand_dims=(0, 2),
            operand_batching_dims=(1,),
            scatter_indices_batching_dims=(0,))
        return jax.lax.scatter(buf, idx, val.astype(buf.dtype), dims)


def _read_layer(cache: dict, layer, dtype):
    """Layer ``layer`` of the stacked cache as K and V (B, C, Hkv, D)."""
    at = lambda name: jax.lax.dynamic_index_in_dim(cache[name], layer, 0,
                                                   keepdims=False)
    if "k_scale" not in cache:
        return at("k"), at("v")
    with jax.named_scope("attn.scores"):
        return (dequantize_kv(at("k"), at("k_scale"), dtype),
                dequantize_kv(at("v"), at("v_scale"), dtype))


def apply_decode(p: dict, x: jax.Array, cfg: ModelConfig, cache: dict,
                 layer: jax.Array, pos: jax.Array, slots=None):
    """Decode step of S ≥ 1 tokens for layer ``layer`` of the stacked cache.

    x (B, S, d); cache: the whole stacked pool, {k, v: (L, B, C, Hkv, D)}
    and, for the int8 KV cache (kv_cache_dtype='int8', §Perf), k_scale,
    v_scale: f16 (L, B, C, Hkv).  pos scalar i32 or a (B,) per-slot
    position vector (continuous batching: every batch row decodes at its
    own depth).  S > 1 writes S rows per slot — row b's tokens land
    at positions pos[b]..pos[b]+S-1 and the causal mask (key slot j visible
    iff j ≤ query position) keeps any stale cache rows beyond the written
    range invisible.

    The new rows are written into the stacked pool first and layer
    ``layer`` is read back after: no read of the old pool stays live across
    the in-place write, which would make XLA copy the pool.

    slots: optional (task_ids, stacked-scale subtree) — mixed-task decode
    reads per-slot scale rows in every quantized linear (linear.apply).
    With S > 1 the caller passes task_ids already repeated per token.

    Returns (out (B, S, d_model), new_cache).
    """
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, slots=slots)
    if cfg.use_rope:
        q, k = _rope_decode(q, k, pos, cfg)
    # token s of row b goes to row pos[b] + s (ring: modulo the capacity)
    rows = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)) + jnp.arange(s), (b, s))
    if cfg.swa_window is not None:
        rows = jnp.mod(rows, cache["k"].shape[2])
    if "k_scale" in cache:
        k8, ks = quantize_kv(k)
        v8, vs = quantize_kv(v)
        new = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    else:
        new = {"k": k, "v": v}
    cache = {name: _cache_write(buf, new[name], layer, rows)
             for name, buf in cache.items()}
    ck, cv = _read_layer(cache, layer, x.dtype)
    # visible = slots with index <= pos (ring: all written slots; dense: prefix)
    o = _scores(q, ck, cv, cfg, offset=pos, impl=cfg.attn_impl)
    return _out(p, o, cfg, slots), cache


def quantize_kv(t: jax.Array):
    """(…, H, D) bf16 → (int8 codes, f16 per-(…,H) scale). Symmetric, the
    standard KV-quant recipe; dequant fuses into the attention dot."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def apply_prefill(p: dict, x: jax.Array, cfg: ModelConfig, cap: int,
                  slots=None):
    """Full-sequence causal attention that also emits the decode cache.

    slots: optional (task_ids, stacked-scale subtree) — a resident-stack
    prefill reads per-row scales in every quantized linear exactly like the
    slotted decode step (task_ids already repeated per token, B·S rows).

    Returns (out (B,S,d_model), ck (B,cap,Hkv,D), cv) with cache in ring
    layout (slot of token t = t % cap; a no-op roll when cap == S).
    """
    s = x.shape[1]
    q, k, v = _qkv(p, x, cfg, slots=slots)
    if cfg.use_rope:
        q, k = _rope_full(q, k, jnp.arange(s), cfg)
    o = _scores(q, k, v, cfg, window=cfg.swa_window)
    out = _out(p, o, cfg, slots)
    with jax.named_scope("attn.kv_write"):
        ck = jnp.roll(k[:, s - cap:], s % cap, axis=1).astype(x.dtype)
        cv = jnp.roll(v[:, s - cap:], s % cap, axis=1).astype(x.dtype)
    return out, ck, cv


def prefill_cache_entry(ck, cv, cfg: ModelConfig) -> dict:
    """Package prefill K/V into the configured cache layout."""
    if cfg.kv_cache_dtype == "int8":
        k8, ks = quantize_kv(ck)
        v8, vs = quantize_kv(cv)
        return {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    return {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_init(rng, cfg: ModelConfig) -> dict:
    return init(rng, cfg)


def cross_apply(p: dict, x: jax.Array, enc: jax.Array, cfg: ModelConfig
                ) -> jax.Array:
    """x: (B, S, d) decoder states; enc: (B, T, d) encoder output."""
    spec = cfg.quant.spec()
    b, s, _ = x.shape
    t = enc.shape[1]
    dh = cfg.d_head
    q = linear.apply(p["wq"], x, spec).reshape(b, s, cfg.n_heads, dh)
    k = linear.apply(p["wk"], enc, spec).reshape(b, t, cfg.n_kv_heads, dh)
    v = linear.apply(p["wv"], enc, spec).reshape(b, t, cfg.n_kv_heads, dh)
    o = ops.attention(q, k, v, causal=False)
    o = o.reshape(b, s, cfg.n_heads * dh)
    return linear.apply(p["wo"], o, spec, row_parallel=True)
