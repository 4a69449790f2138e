"""Mixture-of-Experts block: token-choice top-k, dropless grouped dispatch.

Covers the MoE archs:
  * mixtral-8x7b      — 8 experts, top-2, no shared experts, SWA attention
  * deepseek-moe-16b  — 64 fine-grained routed experts top-6 + 2 shared
                        experts (dense MLPs always applied), one leading
                        dense layer
  * DeepSeek-V2-Lite  — as deepseek-moe-16b, gate weights not renormalised

Routing is DROPLESS: every token's top k experts run, however uneven the
load.  The T·K assignments are sorted by expert and each expert's rows
padded to whole ``bm``-row tiles (``_group``), so the padding is at most
``bm − 1`` rows an expert, never an (E × capacity) block.  The experts'
quantized linears run as one grouped call each
(``ops.grouped_quant_matmul``): the Pallas kernel reads every touched
expert's codes once and skips the others; the plain path is a
``ragged_dot`` over the dequantized experts.  Each row may carry its own
task (``slots``): the kernel's epilogue then applies that task's expert
scales, as the task-stacked GEMV does for a dense linear.

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
      so each shard groups the assignments to its own experts locally,
      computes, and the same single psum combines contributions.  No
      all-to-all needed at all — an explicit design choice enabled by
      TP-replicated activations; see DESIGN.md §4.

Router stays fp32 and un-quantized (DESIGN.md §Arch-applicability).
Aux load-balance loss follows Switch: E · Σ_e f_e · p_e.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.dist import context as dctx
from repro.models import common, linear


def _expert_mlp_init(rng, cfg: ModelConfig, d_ff: int) -> dict:
    """Stacked expert FFNs: every leaf gets a leading n_experts dim."""
    e = cfg.moe.n_experts
    rngs = jax.random.split(rng, e)
    return jax.vmap(lambda r: common.mlp_init(r, cfg, d_ff=d_ff))(rngs)


def experts_key(cfg: ModelConfig) -> str:
    return "experts_ep" if cfg.moe.expert_sharding == "expert" else "experts"


def init(rng, cfg: ModelConfig) -> dict:
    mc = cfg.moe
    d_ff = mc.d_ff_expert or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "router": {"w": (jax.random.normal(ks[0], (mc.n_experts, cfg.d_model))
                         * cfg.d_model ** -0.5).astype(jnp.float32)},
        experts_key(cfg): _expert_mlp_init(ks[1], cfg, d_ff),
    }
    if mc.n_shared_experts:
        p["shared"] = common.mlp_init(ks[2], cfg, d_ff=d_ff * mc.n_shared_experts)
    return p


def route(xt: jax.Array, router_w: jax.Array, mc):
    """xt (T, d) → (gate_idx (T,K) i32, gate_vals (T,K) f32, probs (T,E)).
    Softmax over all experts in float32; the top k's weights are
    renormalised (``norm_topk_prob``) or scaled by
    ``routed_scaling_factor``, as DeepSeek-V2 has it."""
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,ed->te", xt.astype(jnp.float32), router_w,
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, mc.top_k)
        if mc.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-20)
        else:
            gate_vals = gate_vals * mc.routed_scaling_factor
        return gate_idx, gate_vals, probs


class Groups(NamedTuple):
    """The T·K assignments grouped by expert (``_group``)."""
    row_of: jax.Array        # (T, K) padded row of each assignment (M = none)
    token_of_row: jax.Array  # (M,) token of each row (T = padding)
    sizes: jax.Array         # (E,) rows of each expert, padded to bm
    counts: jax.Array        # (E,) assignments of each expert
    tile_expert: jax.Array   # (M // bm,) expert of each tile
    tiles_used: jax.Array    # () tiles that hold rows
    block_m: int


def block_rows(assignments: int, n_experts: int) -> int:
    """Rows a tile: the mean load of an expert rounded up to a power of
    two, from 16 (a bf16 sublane tile; decode) to 128 (prefill)."""
    mean = -(-assignments // n_experts)
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def _group(gate_idx: jax.Array, lo, n_local: int, bm: int) -> Groups:
    """Sort the assignments by expert, keep those of experts
    ``[lo, lo + n_local)``, and pad each expert's rows to whole tiles.
    Static size: M = bm · (T·K // bm + min(E, T·K)) rows bound every
    padding, since Σ_e ⌈c_e / bm⌉ ≤ ⌊Σ_e c_e / bm⌋ + #(c_e > 0)."""
    t, k = gate_idx.shape
    tk = t * k
    m = bm * (tk // bm + min(n_local, tk))
    flat_e = gate_idx.reshape(tk) - lo
    mine = (flat_e >= 0) & (flat_e < n_local)
    flat_e = jnp.where(mine, flat_e, n_local)          # others sort last
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=n_local + 1)[:n_local].astype(
        jnp.int32)
    sizes = (counts + bm - 1) // bm * bm
    ends = jnp.cumsum(sizes)
    e_of = jnp.minimum(sorted_e, n_local - 1)
    rank = jnp.arange(tk, dtype=jnp.int32) - (jnp.cumsum(counts)
                                              - counts)[e_of]
    dest = jnp.where(sorted_e < n_local, ends[e_of] - sizes[e_of] + rank, m)
    row_of = jnp.zeros(tk, jnp.int32).at[order].set(dest).reshape(t, k)
    token_of_row = jnp.full((m + 1,), t, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32))[:m]
    used = ends[-1] // bm
    starts = jnp.minimum(jnp.arange(m // bm, dtype=jnp.int32) * bm,
                         jnp.maximum(ends[-1] - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, starts, side="right"), n_local - 1
    ).astype(jnp.int32)
    return Groups(row_of, token_of_row, sizes, counts, tile_expert, used, bm)


def _experts_apply(experts: dict, xs: jax.Array, grp: Groups,
                   cfg: ModelConfig, slots=None) -> jax.Array:
    """The experts' gated MLP over rows ``xs`` (M, d) grouped by ``grp``.
    ``slots``: (row task ids (M,), expert scale stack subtree) or None."""
    from repro.kernels import ops
    spec = cfg.quant.spec()

    def lin(name, x):
        p = experts[name]
        ent = linear.slot_entry(slots, name)
        if "qw" not in p:        # full-precision experts (tuning mode full)
            w = jnp.swapaxes(p["w"].astype(x.dtype), 1, 2)
            return jax.lax.ragged_dot(x, w, grp.sizes,
                                      preferred_element_type=jnp.float32
                                      ).astype(x.dtype)
        if ent is None:
            scale, zero, tids = p["scale"], p["zero"], None
        else:
            tids, st = ent
            scale, zero = st["scale"], st["zero"]
        (qw, scale, zero), layer = linear.in_place(p["qw"], scale, zero)
        return ops.grouped_quant_matmul(
            x, qw, scale, zero, grp.sizes, grp.tile_expert,
            grp.tiles_used, spec, block_m=grp.block_m, task_ids=tids,
            layer=layer)

    with jax.named_scope("moe.experts"):
        up = lin("up", xs)
        if "gate" in experts:
            h = jax.nn.silu(lin("gate", xs)) * up
        else:
            h = jax.nn.gelu(up)
        return lin("down", h)


def _moe_math(p: dict, xt: jax.Array, cfg: ModelConfig,
              model_axis: Optional[str], data_axes: tuple, slots=None,
              one_task: bool = False):
    """Shard-local MoE math. xt (T_local, d). Returns (y, aux, stats) — y
    still a PARTIAL sum over `model_axis` (caller psums once, together with
    the shared-expert partial); stats (3,) int32: experts with a row, rows
    routed, the fullest expert's rows (this shard's experts).

    ``slots``: (task ids (T,), the experts' scale-stack subtree) or None;
    ``one_task``: every token has the first token's task (a batch-1
    prefill), so the experts read that task's scales as plain scales."""
    mc = cfg.moe
    t, d = xt.shape
    e, k = mc.n_experts, mc.top_k

    gate_idx, gate_vals, probs = route(xt, p["router"]["w"], mc)
    experts = p[experts_key(cfg)]
    first = experts["up"]
    e_local = (first["w"] if "w" in first else first["qw"]).shape[0]
    lo = 0
    if e_local < e and model_axis is not None:
        # expert-parallel: this shard serves experts [lo, lo + e_local)
        lo = jax.lax.axis_index(model_axis) * e_local
    grp = _group(gate_idx, lo, e_local, block_rows(t * k, e))

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xs = xpad[grp.token_of_row]                                  # gather
    row_slots = None
    if slots is not None:
        tids, stack = slots
        if one_task:
            experts = {name: {**lin, **jax.tree.map(
                lambda a: jnp.take(linear.plain(a), tids[0], axis=1),
                stack[name])}
                for name, lin in experts.items()}
        else:
            tpad = jnp.concatenate([tids, jnp.zeros((1,), tids.dtype)])
            row_slots = (tpad[grp.token_of_row], stack)
    ys = _experts_apply(experts, xs, grp, cfg, row_slots)
    # combine: each token gathers its k rows (a row of zeros for another
    # shard's experts) and weighs them
    ypad = jnp.concatenate([ys, jnp.zeros((1, d), ys.dtype)], axis=0)
    rows = ypad[jnp.minimum(grp.row_of, ys.shape[0])]            # (T, K, d)
    y = jnp.einsum("tk,tkd->td", gate_vals, rows.astype(jnp.float32))
    stats = jnp.stack([jnp.sum(grp.counts > 0), jnp.sum(grp.counts),
                       jnp.max(grp.counts)]).astype(jnp.int32)

    # Switch aux loss (identical across model shards; make it shard-invariant)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).sum(1), axis=0)
    aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    if data_axes:  # aux varies only over data axes (tokens); model-invariant
        aux = jax.lax.pmean(aux, tuple(data_axes))
    return y.astype(xt.dtype), aux, stats


def _moe_local(p: dict, x: jax.Array, cfg: ModelConfig,
               model_axis: Optional[str], data_axes: tuple,
               seq_sharded: bool = False, slots=None):
    """Full block on local shards.  Returns (y, aux, stats).

    Sharded path (inside shard_map): x arrives (b_l, s_l, d) — batch split
    over data axes AND seq split over 'model' (the SP layout the surrounding
    blocks keep activations in).  We all-gather tokens over 'model' (cheap:
    same bytes the dense block's SP all-gather costs), dispatch LOCALLY to
    this shard's experts (EP slice or d_ff slice), and psum-SCATTER the
    combined partial outputs straight back into SP layout — exactly one
    all-gather + one reduce-scatter per MoE block, the same collective bill
    as a dense Megatron-SP MLP.  (A token-granular all-to-all variant is the
    §Perf hillclimb; see EXPERIMENTS.md.)

    slots: (task ids (B,), the block's scale-stack subtree) — each token's
    experts and shared experts read its task's scales (off the mesh only).
    """
    from repro.kernels import ops
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if model_axis is not None and seq_sharded:
        xt = jax.lax.all_gather(xt, model_axis, axis=0, tiled=True)
    tok_slots = None
    if slots is not None:
        tids, stack = slots
        tok_slots = (jnp.repeat(tids, s), stack)
    with ops.force_impl("autodiff" if model_axis is not None
                        else ops.default_impl()):
        y, aux, stats = _moe_math(
            p, xt, cfg, model_axis, data_axes,
            linear.slot_entry(tok_slots, experts_key(cfg)), one_task=b == 1)
        if "shared" in p:
            with jax.named_scope("moe.shared"):
                # partial over model
                y = y + common.mlp_apply(
                    p["shared"], xt, cfg,
                    slots=linear.slot_entry(tok_slots, "shared"))
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
    return y.reshape(b, s, d), aux, stats


def apply(p: dict, x: jax.Array, cfg: ModelConfig, slots=None):
    """x: (B, S, d) → (out (B, S, d), aux scalar, stats): the routing stats
    (3,) int32 (experts with a row, rows routed, the fullest expert's
    rows) off the mesh, None on it.  Shard-mapped when a mesh context is
    active; plain local math otherwise (tests, CPU examples, one chip).

    slots: (task ids (B,), the block's scale-stack subtree) for the
    resident-stack prefill and decode, off the mesh only."""
    ctx = dctx.current()
    if ctx is None:
        return _moe_local(p, x, cfg, None, (), slots=slots)
    if slots is not None:
        raise NotImplementedError(
            "per-slot task scales run off the mesh only")
    p = linear.plain_tree(p)      # shard_map takes arrays, not LayerOf

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
    def fn(pp, xx):
        y, aux, _ = _moe_local(pp, xx, cfg, m,
                               dp if batch_sharded else (), seq_sharded)
        return y, aux
    y, aux = shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(in_specs_p, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p, x)
    return y, aux, None
