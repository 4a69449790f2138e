"""Mixture-of-Experts block: token-choice top-k, sort-based capacity dispatch.

Covers both assigned MoE archs:
  * mixtral-8x7b      — 8 experts, top-2, no shared experts, SWA attention
  * deepseek-moe-16b  — 64 fine-grained routed experts top-6 + 2 shared
                        experts (dense MLPs always applied)

Dispatch is SORT-based (MegaBlocks/MaxText lineage), not the one-hot-einsum
formulation: the (T, E, C) dispatch einsum costs T·E·C·d FLOPs — for
mixtral train_4k that is ~50% of the expert FFN FLOPs itself.  Sorting the
T·K assignments by expert id and gathering/scatter-adding costs O(T·K·d)
data movement and ~0 FLOPs.

Distribution runs the block inside ``jax.shard_map`` (when a mesh context is
active) so dispatch stays shard-LOCAL:

  'tensor' sharding (mixtral, E ∤ mp): every shard holds all E experts with
      d_ff sliced over 'model'; expert FFN produces partial sums; combine is
      linear, so we combine FIRST and psum ONE (T_local, d) tensor — the
      same collective bytes as a dense Megatron MLP.  ``down`` codes whose
      word shards would cut a packing tile (core.quant.splits_on_tiles)
      enter whole, and each shard cuts out its own d_ff columns.
  'expert' sharding (deepseek, E % mp == 0): each model shard holds E/mp
      experts; activations are replicated over 'model' (Megatron invariant),
      so each shard dispatches to its own experts locally, computes, and the
      same single psum combines contributions.  No all-to-all needed at all
      — an explicit design choice enabled by TP-replicated activations; see
      DESIGN.md §4.

Router stays fp32 and un-quantized (DESIGN.md §Arch-applicability).
Aux load-balance loss follows Switch: E · Σ_e f_e · p_e.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.dist import context as dctx
from repro.models import common


def _expert_mlp_init(rng, cfg: ModelConfig, d_ff: int) -> dict:
    """Stacked expert FFNs: every leaf gets a leading n_experts dim."""
    e = cfg.moe.n_experts
    rngs = jax.random.split(rng, e)
    return jax.vmap(lambda r: common.mlp_init(r, cfg, d_ff=d_ff))(rngs)


def init(rng, cfg: ModelConfig) -> dict:
    mc = cfg.moe
    d_ff = mc.d_ff_expert or cfg.d_ff
    ks = jax.random.split(rng, 3)
    experts_key = "experts_ep" if mc.expert_sharding == "expert" else "experts"
    p = {
        "router": {"w": (jax.random.normal(ks[0], (mc.n_experts, cfg.d_model))
                         * cfg.d_model ** -0.5).astype(jnp.float32)},
        experts_key: _expert_mlp_init(ks[1], cfg, d_ff),
    }
    if mc.n_shared_experts:
        p["shared"] = common.mlp_init(ks[2], cfg, d_ff=d_ff * mc.n_shared_experts)
    return p


def _route(xt: jax.Array, router_w: jax.Array, k: int):
    """xt (T, d) → (gate_idx (T,K) i32, gate_vals (T,K) f32, probs (T,E))."""
    logits = jnp.einsum("td,ed->te", xt.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_idx, gate_vals, probs


def _sort_dispatch(gate_idx: jax.Array, e: int, cap: int):
    """Assignment → (expert, slot) mapping via a stable sort.

    Returns (token_for_slot (e*cap,) i32 with sentinel T for empty slots,
             pos_orig (T,K) slot within expert, keep_orig (T,K) bool).
    """
    t, k = gate_idx.shape
    tk = t * k
    flat_e = gate_idx.reshape(tk)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    counts = jnp.bincount(flat_e, length=e)
    seg_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - seg_start[sorted_e]
    keep_sorted = pos_sorted < cap
    slot = jnp.where(keep_sorted, sorted_e * cap + pos_sorted, e * cap)
    token_for_slot = jnp.full((e * cap + 1,), t, jnp.int32).at[slot].set(sorted_t)
    # invert the sort to address per-assignment slots in original order
    pos_orig = jnp.zeros(tk, jnp.int32).at[order].set(pos_sorted).reshape(t, k)
    keep_orig = jnp.zeros(tk, bool).at[order].set(keep_sorted).reshape(t, k)
    return token_for_slot[:-1], pos_orig, keep_orig


def _moe_math(p: dict, xt: jax.Array, cfg: ModelConfig,
              model_axis: Optional[str], data_axes: tuple):
    """Shard-local MoE math. xt (T_local, d). Returns (y, aux) — y still a
    PARTIAL sum over `model_axis` (caller psums once, together with the
    shared-expert partial)."""
    mc = cfg.moe
    t, d = xt.shape
    e, k = mc.n_experts, mc.top_k
    cap = max(min(int(t * k / e * mc.capacity_factor), t), 1)

    gate_idx, gate_vals, probs = _route(xt, p["router"]["w"], k)
    token_for_slot, pos_orig, keep_orig = _sort_dispatch(gate_idx, e, cap)

    experts = p.get("experts_ep", p.get("experts"))
    e_local = experts["up"]["w" if "w" in experts["up"] else "qw"].shape[0]
    if "experts_ep" in p and model_axis is not None and e_local < e:
        # expert-parallel: this shard serves experts [lo, lo + e_local)
        shard = jax.lax.axis_index(model_axis)
        lo = shard * e_local
        token_for_slot = jax.lax.dynamic_slice_in_dim(
            token_for_slot, lo * cap, e_local * cap)
        my_assign = (gate_idx >= lo) & (gate_idx < lo + e_local)
        local_eidx = jnp.clip(gate_idx - lo, 0, e_local - 1)
        keep_local = keep_orig & my_assign
    else:
        local_eidx = gate_idx
        keep_local = keep_orig

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xin = xpad[token_for_slot].reshape(e_local, cap, d)          # gather
    xout = jax.vmap(lambda ep_, xe: common.mlp_apply(ep_, xe, cfg))(experts, xin)

    # combine: per-assignment gather from expert outputs, weighted scatter-add
    flat_idx = (local_eidx * cap + pos_orig).reshape(-1)         # (T*K,)
    contrib = xout.reshape(e_local * cap, d)[jnp.clip(flat_idx, 0, e_local * cap - 1)]
    w = (gate_vals * keep_local).reshape(-1, 1).astype(jnp.float32)
    contrib = contrib.astype(jnp.float32) * w
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)

    # Switch aux loss (identical across model shards; make it shard-invariant)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).sum(1), axis=0)
    aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    if data_axes:  # aux varies only over data axes (tokens); model-invariant
        aux = jax.lax.pmean(aux, tuple(data_axes))
    return y.astype(xt.dtype), aux


def _moe_local(p: dict, x: jax.Array, cfg: ModelConfig,
               model_axis: Optional[str], data_axes: tuple,
               seq_sharded: bool = False):
    """Full block on local shards.

    Sharded path (inside shard_map): x arrives (b_l, s_l, d) — batch split
    over data axes AND seq split over 'model' (the SP layout the surrounding
    blocks keep activations in).  We all-gather tokens over 'model' (cheap:
    same bytes the dense block's SP all-gather costs), dispatch LOCALLY to
    this shard's experts (EP slice or d_ff slice), and psum-SCATTER the
    combined partial outputs straight back into SP layout — exactly one
    all-gather + one reduce-scatter per MoE block, the same collective bill
    as a dense Megatron-SP MLP.  (A token-granular all-to-all variant is the
    §Perf hillclimb; see EXPERIMENTS.md.)
    """
    from repro.kernels import ops
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if model_axis is not None and seq_sharded:
        xt = jax.lax.all_gather(xt, model_axis, axis=0, tiled=True)
    with ops.force_impl("autodiff" if model_axis is not None
                        else ops.default_impl()):
        y, aux = _moe_math(p, xt, cfg, model_axis, data_axes)
        if "shared" in p:
            y = y + common.mlp_apply(p["shared"], xt, cfg)  # partial over model
    if model_axis is not None:
        if seq_sharded:
            y = jax.lax.psum_scatter(y, model_axis, scatter_dimension=0,
                                     tiled=True)
            # aux was computed from the all-gathered tokens: equal on every
            # model shard but typed varying — pmean is a value no-op that
            # restores the invariance the P() out_spec needs
            aux = jax.lax.pmean(aux, model_axis)
        else:
            y = jax.lax.psum(y, model_axis)
    return y.reshape(b, s, d), aux


def apply(p: dict, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, d) → (out (B, S, d), aux scalar). Shard-mapped when a mesh
    context is active; plain local math otherwise (tests, CPU examples)."""
    ctx = dctx.current()
    if ctx is None:
        return _moe_local(p, x, cfg, None, ())

    mc = cfg.moe
    dp = ctx.data_axes
    m = ctx.model_axis
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    msize = sizes[m]
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    b, s, _ = x.shape
    batch_sharded = b % dp_size == 0
    seq_sharded = cfg.seq_shard and s % sizes[m] == 0 and s > 1
    qspec = cfg.quant.spec()
    cut_in_shard = set()   # subtrees whose `down` codes arrive whole

    def at(dim: int) -> P:
        return P(*([None] * dim), m)

    def leaf_spec(top: str, name: str, key: str, leaf) -> P:
        """Expert-parallel leaves shard the expert dim; otherwise Megatron:
        up/gate shard their output (d_ff), down its contraction (d_ff)."""
        nd = leaf.ndim
        if top == "experts_ep":
            return at(0)
        if name != "down":
            if key in ("w", "qw", "scale", "zero"):
                return at(nd - 2)
            return at(nd - 1) if key == "b" else P()
        if key == "w":
            return at(nd - 1)
        if key == "qw":
            # a word shard is this shard's d_ff columns only when it holds
            # whole packing tiles; else the codes come whole and each shard
            # cuts its own columns out (quant.local_columns)
            if quant.splits_on_tiles(leaf.shape[-1] * qspec.fields, qspec,
                                     msize):
                return at(nd - 1)
            cut_in_shard.add(top)
            return P()
        if key in ("scale", "zero") and leaf.shape[-1] > 1:
            if leaf.shape[-1] % msize:
                raise ValueError(
                    f"moe/{top}/down/{key}: {leaf.shape[-1]} quant groups "
                    f"do not split over the {msize}-way model axis")
            return at(nd - 1)   # this shard's groups of d_ff
        return P()                  # per-channel scale/zero: per output row

    in_specs_p = {}
    for top, sub in p.items():
        if top == "router":
            in_specs_p[top] = jax.tree.map(lambda l: P(), sub)
        else:
            in_specs_p[top] = {
                name: {key: leaf_spec(top, name, key, leaf)
                       for key, leaf in mats.items()}
                for name, mats in sub.items()}
    x_spec = P(dp if batch_sharded else None,
               m if seq_sharded else None, None)

    def local(pp, xx):
        for top in cut_in_shard:
            down = dict(pp[top]["down"])
            down["qw"] = quant.local_columns(
                down["qw"], qspec, jax.lax.axis_index(m), msize)
            pp = {**pp, top: {**pp[top], "down": down}}
        return fn(pp, xx)

    # aux pmean must run only over axes the values actually vary on
    fn = partial(_moe_local, cfg=cfg, model_axis=m,
                 data_axes=dp if batch_sharded else (),
                 seq_sharded=seq_sharded)
    y, aux = shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(in_specs_p, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p, x)
    return y, aux
