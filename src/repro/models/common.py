"""Shared neural building blocks: norms, RoPE, MLPs, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import linear


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    p = {"g": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["b"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["g"] + p["b"]
    else:  # rmsnorm
        y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + cfg.norm_eps)
        y = y * p["g"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, d: int | None = None) -> jax.Array:
    """Inverse frequencies of a ``d``-wide rotary embedding; YaRN-blended
    when ``cfg.rope_scaling`` is set (``yarn_freqs``)."""
    d = d or cfg.d_head
    if cfg.rope_scaling is not None:
        return yarn_freqs(cfg.rope_theta, d, cfg.rope_scaling)
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1·mscale·ln(factor) + 1``."""
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(theta: float, d: int, rs) -> jax.Array:
    """YaRN inverse frequencies (Peng et al. 2023, as DeepSeek-V2 has
    them): dims that turn more than ``beta_fast`` times over the original
    context keep their frequency, dims that turn fewer than ``beta_slow``
    times are divided by ``factor``, and a linear ramp blends between."""
    def dim_at(turns):
        return (d * math.log(rs.original_max_position_embeddings
                             / (turns * 2 * math.pi))) / (2 * math.log(theta))
    lo = max(math.floor(dim_at(rs.beta_fast)), 0)
    hi = min(math.ceil(dim_at(rs.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    base = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (rs.factor * base)) * (1.0 - keep) + (1.0 / base) * keep


def rope_mscale(cfg: ModelConfig) -> float:
    """Factor on the rotated cos/sin (YaRN's ``mscale`` over
    ``mscale_all_dim``; 1 without scaling)."""
    rs = cfg.rope_scaling
    if rs is None:
        return 1.0
    return (yarn_mscale(rs.factor, rs.mscale)
            / yarn_mscale(rs.factor, rs.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, freqs: jax.Array) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or scalar absolute positions."""
    positions = jnp.asarray(positions, jnp.float32)
    if positions.ndim == 0:
        positions = positions[None]
    ang = positions[:, None] * freqs[None, :]          # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_slots(x: jax.Array, positions: jax.Array,
                     freqs: jax.Array) -> jax.Array:
    """Per-slot RoPE for continuous decode: x (B, S, H, D), positions (B,).

    Each batch row sits at its OWN absolute position (the slot-pool decode
    step of ``train/serve.py`` — sequences admitted at different times are
    at different depths).  ``apply_rope`` cannot express this: its
    ``positions`` index the sequence dim, shared across the batch.
    Token s of row b is at absolute position ``positions[b] + s`` (S > 1:
    S consecutive tokens per slot, each at its own position).
    """
    pos = jnp.asarray(positions, jnp.float32)
    pos = pos[:, None] + jnp.arange(x.shape[1], dtype=jnp.float32)[None, :]
    ang = pos[..., None] * freqs                       # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------

def mlp_init(rng, cfg: ModelConfig, d_in: int | None = None,
             d_ff: int | None = None) -> dict:
    d_in = d_in or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "up": linear.init(ks[0], d_in, d_ff),
        "down": linear.init(ks[1], d_ff, d_in),
    }
    if cfg.act == "silu":  # gated (SwiGLU family)
        p["gate"] = linear.init(ks[2], d_in, d_ff)
    return p


def mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig,
              slots=None) -> jax.Array:
    """slots: optional (task_ids, stacked-scale subtree) for the mixed-task
    decode step — threaded into each quantized linear (see linear.apply)."""
    with jax.named_scope("mlp"):
        spec = cfg.quant.spec()
        up = linear.apply(p["up"], x, spec,
                          slots=linear.slot_entry(slots, "up"))
        if "gate" in p:
            gate = linear.apply(p["gate"], x, spec,
                                slots=linear.slot_entry(slots, "gate"))
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        return linear.apply(p["down"], h, spec,
                            slots=linear.slot_entry(slots, "down"),
                            row_parallel=True)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embed_init(rng, cfg: ModelConfig) -> dict:
    emb = jax.random.normal(rng, (cfg.vocab_size, cfg.d_model)) * 0.02
    return {"emb": emb.astype(jnp.float32)}


def embed_apply(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    return p["emb"].astype(dtype)[tokens]


def head_init(rng, cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    return {"lm_head": linear.init(rng, cfg.d_model, cfg.vocab_size)}


def head_apply(p_head: dict, p_embed: dict, x: jax.Array, cfg: ModelConfig,
               slots=None) -> jax.Array:
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", x,
                              p_embed["emb"].astype(x.dtype),
                              preferred_element_type=jnp.float32)
        y = linear.apply(p_head["lm_head"], x, cfg.quant.spec(), slots=slots)
        return y.astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Token-mean cross entropy; logits (..., V) f32, labels (...) int32.

    Gold-logit extraction uses a one-hot contraction, NOT take_along_axis:
    with the vocab dim sharded over 'model' (dist/sharding.py), a gather
    along the sharded dim would force GSPMD to all-gather the logits; the
    one-hot form reduces locally and psums a scalar."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
