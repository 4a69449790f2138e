"""Whisper-style encoder-decoder backbone (whisper-medium assignment).

Per the assignment the conv/log-mel frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, enc_frames, d_model).  The
transformer backbone is full: non-causal encoder, causal decoder with
cross-attention, LayerNorm + GELU, learned positional embeddings (whisper
has no RoPE → cfg.use_rope = False), tied decoder embeddings.

Decode caches: per-decoder-layer self-attn K/V (capacity = target seq) and
the cross-attn K/V computed ONCE from the encoder output at prefill.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common


def _enc_block_init(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 2)
    return {
        "ln1": common.norm_init(cfg),
        "attn": attention.init(ks[0], cfg),
        "ln2": common.norm_init(cfg),
        "mlp": common.mlp_init(ks[1], cfg),
    }


def _dec_block_init(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 3)
    return {
        "ln1": common.norm_init(cfg),
        "attn": attention.init(ks[0], cfg),
        "ln2": common.norm_init(cfg),
        "xattn": attention.cross_init(ks[1], cfg),
        "ln3": common.norm_init(cfg),
        "mlp": common.mlp_init(ks[2], cfg),
    }


def init(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 6)
    enc_layers = jax.vmap(lambda r: _enc_block_init(r, cfg))(
        jax.random.split(ks[0], cfg.enc_layers))
    dec_layers = jax.vmap(lambda r: _dec_block_init(r, cfg))(
        jax.random.split(ks[1], cfg.n_layers))
    return {
        "enc": {
            "pos": (jax.random.normal(ks[2], (cfg.enc_frames, cfg.d_model))
                    * 0.02).astype(jnp.float32),
            "layers": enc_layers,
            "final_norm": common.norm_init(cfg),
        },
        "dec": {
            "embed": common.embed_init(ks[3], cfg),
            "pos": (jax.random.normal(ks[4], (cfg.max_seq, cfg.d_model))
                    * 0.02).astype(jnp.float32),
            "layers": dec_layers,
            "final_norm": common.norm_init(cfg),
        },
    }


def encode(params: dict, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """frames: (B, T, d) stub embeddings → encoder states (B, T, d)."""
    enc = params["enc"]
    h = frames.astype(jnp.dtype(cfg.dtype)) + enc["pos"].astype(frames.dtype)

    def body(h, layer_p):
        hin = common.norm_apply(layer_p["ln1"], h, cfg)
        q, k, v = attention._qkv(layer_p["attn"], hin, cfg)
        from repro.kernels import ops
        from repro.models import linear
        o = ops.attention(q, k, v, causal=False)
        o = o.reshape(*h.shape[:2], cfg.n_heads * cfg.d_head)
        h = h + linear.apply(layer_p["attn"]["wo"], o, cfg.quant.spec(),
                                 row_parallel=True)
        h = h + common.mlp_apply(layer_p["mlp"],
                                 common.norm_apply(layer_p["ln2"], h, cfg), cfg)
        return h, None

    if cfg.remat in ("block", "full"):
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, enc["layers"])
    return common.norm_apply(enc["final_norm"], h, cfg)


def _dec_embed(params: dict, tokens: jax.Array, pos0, cfg: ModelConfig):
    dec = params["dec"]
    h = common.embed_apply(dec["embed"], tokens, cfg)
    s = tokens.shape[1]
    pos = jax.lax.dynamic_slice_in_dim(dec["pos"], pos0, s, axis=0)
    return h + pos.astype(h.dtype)


def forward(params: dict, frames: jax.Array, tokens: jax.Array,
            cfg: ModelConfig) -> jax.Array:
    """Teacher-forced training forward → logits (B, S, V)."""
    enc_out = encode(params, frames, cfg)
    dec = params["dec"]
    h = _dec_embed(params, tokens, 0, cfg)

    def body(h, layer_p):
        h = h + attention.apply_train(
            layer_p["attn"], common.norm_apply(layer_p["ln1"], h, cfg), cfg)
        h = h + attention.cross_apply(
            layer_p["xattn"], common.norm_apply(layer_p["ln2"], h, cfg),
            enc_out, cfg)
        h = h + common.mlp_apply(
            layer_p["mlp"], common.norm_apply(layer_p["ln3"], h, cfg), cfg)
        return h, None

    if cfg.remat in ("block", "full"):
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, dec["layers"])
    h = common.norm_apply(dec["final_norm"], h, cfg)
    return common.head_apply({}, dec["embed"], h,
                             cfg.replace(tie_embeddings=True))


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch["frames"], batch["tokens"], cfg)
    return common.cross_entropy(logits, batch["labels"], batch.get("mask"))


def prefill(params: dict, frames: jax.Array, tokens: jax.Array,
            cfg: ModelConfig, last_pos=None):
    """Encode + run decoder over the prompt; build self+cross caches.

    ``last_pos`` (traced int32 scalar): index of the last REAL prompt token
    when the prompt is right-padded to a bucketed length — the head reads
    that row instead of ``[:, -1:]``, so padded rows (causally invisible to
    every real row) never reach the logits.  ``None`` = unpadded prompt.
    """
    enc_out = encode(params, frames, cfg)
    dec = params["dec"]
    b, s = tokens.shape
    h = _dec_embed(params, tokens, 0, cfg)
    cap = s

    def body(h, layer_p):
        hin = common.norm_apply(layer_p["ln1"], h, cfg)
        a, ck, cv = attention.apply_prefill(layer_p["attn"], hin, cfg, cap)
        h = h + a
        hin = common.norm_apply(layer_p["ln2"], h, cfg)
        # cross K/V computed once, cached
        from repro.models import linear
        spec = cfg.quant.spec()
        t = enc_out.shape[1]
        xk = linear.apply(layer_p["xattn"]["wk"], enc_out, spec
                          ).reshape(b, t, cfg.n_kv_heads, cfg.d_head)
        xv = linear.apply(layer_p["xattn"]["wv"], enc_out, spec
                          ).reshape(b, t, cfg.n_kv_heads, cfg.d_head)
        from repro.kernels import ops
        q = linear.apply(layer_p["xattn"]["wq"], hin, spec
                         ).reshape(b, s, cfg.n_heads, cfg.d_head)
        o = ops.attention(q, xk, xv, causal=False)
        o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
        h = h + linear.apply(layer_p["xattn"]["wo"], o, spec,
                                 row_parallel=True)
        h = h + common.mlp_apply(
            layer_p["mlp"], common.norm_apply(layer_p["ln3"], h, cfg), cfg)
        return h, {"k": ck, "v": cv, "xk": xk.astype(h.dtype),
                   "xv": xv.astype(h.dtype)}

    h, cache = jax.lax.scan(body, h, dec["layers"])
    h = common.norm_apply(dec["final_norm"], h, cfg)
    hl = h[:, -1:] if last_pos is None else \
        jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1)
    logits = common.head_apply({}, dec["embed"], hl,
                               cfg.replace(tie_embeddings=True))
    return logits[:, 0], cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    kv = (cfg.n_layers, batch, seq_len, cfg.n_kv_heads, cfg.d_head)
    xkv = (cfg.n_layers, batch, cfg.enc_frames, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "xk": jnp.zeros(xkv, dtype), "xv": jnp.zeros(xkv, dtype)}


def decode_step(params: dict, cache: dict, tokens: jax.Array, pos: jax.Array,
                cfg: ModelConfig):
    """One decoder step against frozen cross K/V + growing self K/V.

    ``pos`` is a scalar (lockstep) or a (B,) per-slot position vector (the
    continuous pool) — the learned positional row is gathered per batch row
    in the vector case; the self-attention cache write/mask already speaks
    both (``attention.apply_decode``)."""
    dec = params["dec"]
    b = tokens.shape[0]
    h = common.embed_apply(dec["embed"], tokens, cfg)
    if jnp.ndim(pos) == 0:
        pe = jax.lax.dynamic_slice_in_dim(dec["pos"], pos, 1, axis=0)[None]
    else:
        pe = dec["pos"][pos][:, None]          # (B, 1, d) per-slot rows
    h = h + pe.astype(h.dtype)

    def body(carry, xs):
        h, kv = carry
        layer_p, xk, xv, layer = xs
        hin = common.norm_apply(layer_p["ln1"], h, cfg)
        a, kv = attention.apply_decode(layer_p["attn"], hin, cfg, kv, layer,
                                       pos)
        h = h + a
        hin = common.norm_apply(layer_p["ln2"], h, cfg)
        from repro.models import linear
        from repro.kernels import ops
        spec = cfg.quant.spec()
        q = linear.apply(layer_p["xattn"]["wq"], hin, spec
                         ).reshape(b, 1, cfg.n_heads, cfg.d_head)
        o = ops.attention(q, xk, xv, causal=False)
        o = o.reshape(b, 1, cfg.n_heads * cfg.d_head)
        h = h + linear.apply(layer_p["xattn"]["wo"], o, spec,
                                 row_parallel=True)
        h = h + common.mlp_apply(
            layer_p["mlp"], common.norm_apply(layer_p["ln3"], h, cfg), cfg)
        return (h, kv), None

    # the self-attention pool rides in the carry (transformer.decode_tokens)
    (h, kv), _ = jax.lax.scan(
        body, (h, {"k": cache["k"], "v": cache["v"]}),
        (dec["layers"], cache["xk"], cache["xv"],
         jnp.arange(cache["k"].shape[0])))
    new_cache = dict(cache, **kv)
    h = common.norm_apply(dec["final_norm"], h, cfg)
    logits = common.head_apply({}, dec["embed"], h,
                               cfg.replace(tie_embeddings=True))
    return logits[:, 0], new_cache
