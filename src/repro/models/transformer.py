"""Decoder-only transformer backbone (dense, MoE, VLM-prefix variants).

Layers are stored STACKED (leading dim = n_layers on every leaf) and executed
with ``jax.lax.scan`` — this keeps the HLO size O(1) in depth (critical for
the 512-device dry-run compiles) and is the standard MaxText-style layout.
``cfg.remat`` wraps the per-layer body in ``jax.checkpoint``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import context as dctx
from repro.kernels import ops
from repro.models import attention, common, linear, mla, moe


def _attn(cfg: ModelConfig):
    """The attention module of a block: latent (``mla``) or GQA."""
    return mla if cfg.mla is not None else attention


def _block_init(rng, cfg: ModelConfig, dense: bool = False) -> dict:
    ks = jax.random.split(rng, 3)
    p = {
        "ln1": common.norm_init(cfg),
        "attn": _attn(cfg).init(ks[0], cfg),
        "ln2": common.norm_init(cfg),
    }
    if cfg.moe is not None and not dense:
        p["moe"] = moe.init(ks[1], cfg)
    else:
        p["mlp"] = common.mlp_init(ks[1], cfg)
    return p


def n_dense(cfg: ModelConfig) -> int:
    """Leading blocks with a dense MLP ahead of the MoE blocks."""
    return cfg.n_dense_layers if cfg.moe is not None else 0


def init(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 4)
    k = n_dense(cfg)
    layer_rngs = jax.random.split(ks[0], cfg.n_layers - k)
    layers = jax.vmap(lambda r: _block_init(r, cfg))(layer_rngs)
    if k:
        layers["dense"] = jax.vmap(lambda r: _block_init(r, cfg, True))(
            jax.random.split(ks[3], k))
    params = {
        "embed": common.embed_init(ks[1], cfg),
        "layers": layers,
        "final_norm": common.norm_init(cfg),
    }
    params.update(common.head_init(ks[2], cfg))
    return params


def stacks(layers: dict) -> list:
    """The layer stacks in order: the leading dense blocks
    (``layers/dense``, where a config has them), then the main blocks."""
    if "dense" not in layers:
        return [layers]
    return [layers["dense"],
            {k: v for k, v in layers.items() if k != "dense"}]


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    return _attn(cfg).init_cache(cfg, batch, seq_len)


def _block_train(layer_p: dict, h: jax.Array, cfg: ModelConfig,
                 positions: Optional[jax.Array]):
    """Pre-norm block, full-sequence. Returns (h, aux_loss)."""
    a = _attn(cfg).apply_train(layer_p["attn"],
                              common.norm_apply(layer_p["ln1"], h, cfg),
                              cfg, positions)
    if cfg.constrain_block_outputs:
        # force the block output (and thus its backward cotangent) into the
        # SP layout: the model-axis cotangent psum becomes a reduce-scatter
        a = dctx.constrain_tokens(a, cfg.seq_shard)
    h = h + a
    hin = common.norm_apply(layer_p["ln2"], h, cfg)
    if "moe" in layer_p:
        m, aux, _ = moe.apply(layer_p["moe"], hin, cfg)
    else:
        m, aux = common.mlp_apply(layer_p["mlp"], hin, cfg), 0.0
    if cfg.constrain_block_outputs:
        m = dctx.constrain_tokens(m, cfg.seq_shard)
    return h + m, aux


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: Optional[jax.Array] = None) -> tuple:
    """Full-sequence forward. Returns (logits f32 (B, S, V), aux_loss).

    prefix_embeds (VLM): (B, P, d) precomputed patch embeddings prepended to
    the token embeddings; total sequence = P + len(tokens).
    """
    h = common.embed_apply(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    s = h.shape[1]
    positions = jnp.arange(s)
    h = dctx.constrain_tokens(h, cfg.seq_shard)

    def body(carry, layer_p):
        h, aux = carry
        h, a = _block_train(layer_p, h, cfg, positions)
        h = dctx.constrain_tokens(h, cfg.seq_shard)
        return (h, aux + a), None

    body_fn = body
    if cfg.remat == "dots":
        # save dot outputs, recompute elementwise — trades residency for a
        # smaller backward-recompute HBM stream (§Perf lever for deep stacks)
        body_fn = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots)
    elif cfg.remat in ("block", "full"):
        body_fn = jax.checkpoint(body, prevent_cse=False)
    aux = 0.0
    for stack in stacks(params["layers"]):
        (h, aux), _ = jax.lax.scan(body_fn, (h, aux), stack)
    h = common.norm_apply(params["final_norm"], h, cfg)
    logits = common.head_apply(params, params["embed"], h, cfg)
    return logits, aux


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits, aux = forward(params, batch["tokens"], cfg,
                          prefix_embeds=batch.get("image_embeds"))
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:       # VLM prefix: loss on text only
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    ce = common.cross_entropy(logits, labels, batch.get("mask"))
    coef = cfg.moe.router_aux_coef if cfg.moe is not None else 0.0
    return ce + coef * aux


def prefill(params: dict, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: Optional[jax.Array] = None,
            task_stack: dict | None = None,
            task_ids: jax.Array | None = None,
            last_pos=None):
    """Prefill: forward over the prompt, building the KV cache.

    task_stack/task_ids: same contract as ``decode_tokens`` — the prompt's
    quantized linears read each batch row's scales from the resident stack
    instead of the live tree, so admitting a request for a resident task
    needs NO host→device scale swap (``task_ids: (B,) int32`` stack rows).

    last_pos (traced int32 scalar): index of the last REAL token in the
    (prefix +) prompt sequence when the prompt is right-padded to a bucket
    length — the head reads that row instead of ``[:, -1:]``.  Padded rows
    sit causally AFTER every real row, so they never influence it; ``None``
    (unpadded) keeps the original path bit-for-bit.

    Returns (last_logits (B, V), cache).
    """
    h = common.embed_apply(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    positions = jnp.arange(s)
    cap = attention.cache_capacity(cfg, s)
    h = dctx.constrain_tokens(h, cfg.seq_shard)
    slotted = task_stack is not None
    # quantized linears flatten (B, S, d) to B·S rows: one id per token
    tok_ids = jnp.repeat(task_ids, s) if slotted else None

    def body(h, xs):
        if slotted:
            layer_p, layer_stack = xs
            slots = (tok_ids, layer_stack)
        else:
            layer_p = xs
            slots = None
        hin = common.norm_apply(layer_p["ln1"], h, cfg)
        if cfg.mla is not None:
            a, entry = mla.apply_prefill(
                layer_p["attn"], hin, cfg,
                slots=linear.slot_entry(slots, "attn"))
        else:
            a, ck, cv = attention.apply_prefill(
                layer_p["attn"], hin, cfg, cap,
                slots=linear.slot_entry(slots, "attn"))
            entry = attention.prefill_cache_entry(ck, cv, cfg)
        h = h + a
        hin = common.norm_apply(layer_p["ln2"], h, cfg)
        if "moe" in layer_p:
            m, _, _ = moe.apply(
                layer_p["moe"], hin, cfg,
                slots=linear.slot_entry((task_ids, layer_stack), "moe")
                if slotted else None)
        else:
            m = common.mlp_apply(layer_p["mlp"], hin, cfg,
                                 slots=linear.slot_entry(slots, "mlp"))
        h = dctx.constrain_tokens(h + m, cfg.seq_shard)
        return h, entry

    if cfg.remat in ("block", "full"):
        body = jax.checkpoint(body, prevent_cse=False)
    caches = []
    p_stacks = stacks(params["layers"])
    t_stacks = stacks(task_stack["layers"]) if slotted else p_stacks
    for ps, ts in zip(p_stacks, t_stacks):
        h, c = jax.lax.scan(body, h, (ps, ts) if slotted else ps)
        caches.append(c)
    cache = caches[0] if len(caches) == 1 else jax.tree.map(
        lambda *a: jnp.concatenate(a), *caches)
    h = common.norm_apply(params["final_norm"], h, cfg)
    # the head sees only the last (real) token: one row per batch element
    head_slots = linear.slot_entry((task_ids, task_stack), "lm_head") \
        if slotted else None
    hl = h[:, -1:] if last_pos is None else \
        jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1)
    logits = common.head_apply(params, params["embed"], hl, cfg,
                               slots=head_slots)
    return logits[:, 0], cache


# the leaves of a quantized linear that its kernel reads in place
_IN_PLACE = ("qw", "scale", "zero")


def _split_in_place(tree: dict) -> tuple:
    """A layer stack split into (the quantized linears' codes and scales,
    the rest): two trees of the same dicts, each leaf in one of them."""
    held, rest = {}, {}
    for key, v in tree.items():
        if isinstance(v, dict):
            held[key], rest[key] = _split_in_place(v)
        elif "qw" in tree and key in _IN_PLACE:
            held[key] = v
        else:
            rest[key] = v
    return held, rest


def _join_in_place(rest: dict, held: dict, index) -> dict:
    """``rest`` (one layer's slice) with ``held``'s stacks added back as
    ``linear.LayerOf`` views of layer ``index``."""
    out = dict(rest)
    for key, v in held.items():
        out[key] = (_join_in_place(rest.get(key, {}), v, index)
                    if isinstance(v, dict) else linear.LayerOf(v, index))
    return out


def decode_tokens(params: dict, cache: dict, tokens: jax.Array,
                   pos: jax.Array, cfg: ModelConfig,
                   task_stack: dict | None = None,
                   task_ids: jax.Array | None = None):
    """Shared decode body: tokens (B, S) at positions pos..pos+S-1 (per-slot
    when pos is (B,)).  Returns (logits (B, S, V) f32, new_cache, stats):
    stats (4,) int32 for a MoE config — MoE layers, and summed over them
    the experts with a row, the rows routed and the fullest expert's rows —
    else None.

    task_stack/task_ids (mixed-task continuous decode): ``task_stack``
    mirrors the params tree pruned to its scale/zero leaves with a task dim
    stacked in front of the trailing (out, G) dims (scale_bank.stack_scales),
    and ``task_ids: (B,) int32`` names the stack row each slot reads — the
    quantized linears gather per-slot scales in-kernel instead of the pool
    draining for a scale swap; MoE blocks give each routed row its slot's
    task (off the mesh only).

    The layer scan slices only the small leaves (norms, biases, the
    router, full-precision weights) out of its ``xs``.  The quantized
    linears' codes and scales, and the whole task stack, are closed over
    and reach each linear as ``linear.LayerOf`` views: the kernels read
    the layer's blocks in place instead of a per-layer copy.  Their index
    counts within a layer group of ``stacks`` (DeepSeek's leading dense
    block, then its MoE blocks); the KV pool's counts over all layers.
    """
    h = common.embed_apply(params["embed"], tokens, cfg)

    slotted = task_stack is not None
    row_ids = task_ids
    if slotted and tokens.shape[1] > 1:
        # quantized linears flatten (B, S, d) row-major to M = B·S rows —
        # repeat each slot's task id per token to match
        row_ids = jnp.repeat(task_ids, tokens.shape[1])

    # The stacked KV pool rides in the carry, not in xs/ys: each layer
    # writes only its new rows into it in place and reads its own layer
    # back, so no layer's whole cache is sliced out or written back.
    def body(held, task_held, first: int, carry, xs):
        h, cache = carry
        rest, i = xs
        layer_p = _join_in_place(rest, held, i)
        layer_stack = _join_in_place({}, task_held, i) if slotted else None
        slots = (row_ids, layer_stack) if slotted else None
        hin = common.norm_apply(layer_p["ln1"], h, cfg)
        a, cache = _attn(cfg).apply_decode(
            layer_p["attn"], hin, cfg, cache, first + i, pos,
            slots=linear.slot_entry(slots, "attn"))
        h = h + a
        hin = common.norm_apply(layer_p["ln2"], h, cfg)
        stats = None
        if "moe" in layer_p:
            # routing stats off the mesh only (None on it)
            m, _, stats = moe.apply(
                layer_p["moe"], hin, cfg,
                slots=linear.slot_entry((task_ids, layer_stack), "moe")
                if slotted else None)
        else:
            m = common.mlp_apply(layer_p["mlp"], hin, cfg,
                                 slots=linear.slot_entry(slots, "mlp"))
        return (h + m, cache), stats

    p_stacks = stacks(params["layers"])
    t_stacks = stacks(task_stack["layers"]) if slotted else [{}] * len(
        p_stacks)
    first = 0
    stats = None
    for ps, ts in zip(p_stacks, t_stacks):
        n = jax.tree.leaves(ps)[0].shape[0]
        held, rest = _split_in_place(ps)
        (h, cache), st = jax.lax.scan(partial(body, held, ts, first),
                                      (h, cache), (rest, jnp.arange(n)))
        first += n
        if st is not None:
            stats = jnp.concatenate([jnp.array([n], jnp.int32),
                                     st.sum(0).astype(jnp.int32)])
    h = common.norm_apply(params["final_norm"], h, cfg)
    head_slots = linear.slot_entry((row_ids, task_stack), "lm_head") \
        if slotted else None
    logits = common.head_apply(params, params["embed"], h, cfg,
                               slots=head_slots)
    return logits, cache, stats


def decode_step(params: dict, cache: dict, tokens: jax.Array, pos: jax.Array,
                cfg: ModelConfig, task_stack: dict | None = None,
                task_ids: jax.Array | None = None):
    """One decode step. tokens (B, 1); pos scalar int32 (next position) or
    (B,) per-slot.  Returns (logits (B, V) f32, new_cache).
    See ``decode_tokens`` for the task_stack/task_ids slotted contract."""
    logits, new_cache, _ = decode_tokens(params, cache, tokens, pos, cfg,
                                         task_stack, task_ids)
    return logits[:, 0], new_cache

