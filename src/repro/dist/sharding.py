"""Path-based partition rules for (quantized) param trees.

One function — ``spec_for_path(path, ndim)`` — decides where every leaf of
every architecture lives on the mesh, keyed on the leaf name and its parent
module name, never on tree position (so it works on full stacked trees,
layer-sliced subtrees inside ``shard_map``, and abstract
``ShapeDtypeStruct`` trees alike).

The PEQA-specific part (docs/DIST.md has the full table):

  * Column-parallel linears (wq/wk/wv/up/gate/…) shard the OUTPUT dim.
    Their packed codes ``qw`` (out, in/8) and per-group ``scale``/``zero``
    (out, G) carry the output dim at position -2, so all three leaves use
    the same rule and each model shard holds the scales for exactly the
    rows it owns — a PEQA task swap (ScaleBank) touches only local bytes.
  * Row-parallel linears (wo/down/out_proj) shard the INPUT (contraction)
    dim — the last dim of both ``w`` (out, in) and ``qw`` (out, in/8)
    (4-bit codes pack 8-per-uint32 along the input dim, so the packed
    extent still divides any axis the fp extent divides).  Codes are
    packed in tiles along K (core.quant), so a ``qw`` shard is the packed
    form of a contiguous column range only when it holds whole tiles
    (``quant.splits_on_tiles``): GSPMD's global view does not care, but
    code that reads shards on their own (``ops._kernel_matmul``, MoE's
    ``shard_map``) checks it.  Their
    ``scale``/``zero`` however are (out, G) — per-OUTPUT-row groups with no
    input dim to slice — so they replicate; at G ≤ in/group_size per row
    this is the cheapest correct layout and keeps the dequant epilogue
    local to each shard's partial sums.
  * Stacked MoE experts: tensor-parallel layouts shard d_ff inside every
    expert (same column/row rules, one extra leading dim); expert-parallel
    layouts (``experts_ep``) shard the EXPERT dim itself for every leaf,
    including scales — each shard owns its experts' scales outright.
  * Embeddings / lm_head shard the vocab dim; norms, routers, LoRA ``a``
    factors, positional tables and the tiny xLSTM scalar-gate projections
    replicate.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.treepath import path_str as _path_str

MODEL_AXIS = "model"

# linears that shard the contraction (input) dim — their outputs are the
# partial sums GSPMD reduces once per block (Megatron layout)
ROW_PARALLEL = ("wo", "down", "out_proj")

# modules that stay replicated wholesale: routers must see every token's
# full logits; sr/sb are sLSTM per-head recurrences (block-diagonal, tiny);
# gi/gf/sw are scalar-gate projections whose output extent (n_heads, 4·d)
# is either indivisible or too small to be worth a collective
REPLICATED_MODULES = ("router", "sr", "sb", "gi", "gf", "sw")

# per-head SSM vectors: shard the trailing heads dim alongside the
# head-sharded x/z projections so the SSD scan stays shard-local
HEAD_VECTOR_LEAVES = ("A_log", "ssm_D", "dt_bias")

_LINEAR_LEAVES = ("w", "qw", "scale", "zero", "b")

# the leading dense blocks of a MoE stack (``layers/dense``): their ``down``
# is stored by OUTPUT rows like a column-parallel linear.  DeepSeek's
# published d_ff 10944 packs into 1368 code words, which a 16-way axis
# cannot split, and the quantized kernel splits such a weight by output
# columns anyway (``ops._kernel_matmul``)
DENSE_LEAD = "dense"


def _mk(ndim: int, axis_at: int) -> P:
    """PartitionSpec with MODEL_AXIS at ``axis_at``, trailing Nones trimmed."""
    if axis_at < 0 or axis_at >= ndim:
        return P()
    return P(*([None] * axis_at), MODEL_AXIS)


def _is_norm(name: str) -> bool:
    return name.startswith("ln") or "norm" in name


def spec_for_path(path: str, ndim: int) -> P:
    """PartitionSpec for the leaf at ``path`` with ``ndim`` dims.

    Rules are relative to the TRAILING dims, so any number of leading stack
    dims (layers, zamba groups, experts) is absorbed automatically.
    """
    parts = [p for p in path.split("/") if p]
    leaf = parts[-1] if parts else ""
    parent = parts[-2] if len(parts) >= 2 else ""

    if any(p in REPLICATED_MODULES for p in parts):
        return P()

    if "experts_ep" in parts:
        # expert-parallel: shard the expert dim for EVERY leaf — including
        # LoRA factors and scales, so each shard owns its experts outright
        # (must match moe.apply's shard_map in_specs).  The expert dim sits
        # just before the leaf's own trailing dims: 1 for bias/norm vectors,
        # 2 for w/qw/scale/zero/lora_a/lora_b.
        trailing = 1 if leaf in ("b", "g") else 2
        return _mk(ndim, ndim - trailing - 1)

    if leaf == "emb":                       # (vocab, d) — vocab-sharded
        return _mk(ndim, ndim - 2)
    if leaf in ("pos", "lora_a") or leaf == "g" or (leaf == "b"
                                                    and _is_norm(parent)):
        return P()
    if leaf in HEAD_VECTOR_LEAVES:          # (…, n_heads)
        return _mk(ndim, ndim - 1)
    if leaf == "lora_b":                    # (…, out, r) — follow out dim
        return _mk(ndim, ndim - 2)

    if leaf in _LINEAR_LEAVES:
        if parent in ROW_PARALLEL and not (parent == "down"
                                           and DENSE_LEAD in parts):
            if leaf in ("w", "qw"):         # (…, out, in) — shard input
                return _mk(ndim, ndim - 1)
            return P()                      # scale/zero/b: per-out-row
        if leaf == "b":                     # column bias follows the output
            return _mk(ndim, ndim - 1)
        return _mk(ndim, ndim - 2)          # w/qw/scale/zero: shard output

    return P()


def param_specs(tree) -> dict:
    """PartitionSpec pytree mirroring ``tree`` (works on abstract trees)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: spec_for_path(_path_str(kp), len(leaf.shape)), tree)


def named_shardings(ctx, tree) -> dict:
    """``param_specs`` as a ``NamedSharding`` pytree on ``ctx.mesh`` — the
    tree you hand to ``jax.device_put`` to home a host param tree on the
    mesh, and the in/out shardings of the serving hot path."""
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                        param_specs(tree),
                        is_leaf=lambda x: isinstance(x, P))


def stacked_scale_specs(tree) -> dict:
    """PartitionSpec tree for a ``core.scale_bank.ResidentStack`` stack.

    Stacked scale/zero leaves carry a task dim inserted just before the
    trailing (out, G) pair — (L, N, G) → (L, T, N, G).  Because the path
    rules above are TRAILING-relative, ``param_specs`` already places them
    correctly: the task dim lands replicated (it is a leading stack dim like
    layers), column-parallel scales shard their out dim exactly like the
    live leaf, and row-parallel scales stay replicated — so a stacked row
    install moves the same per-shard bytes as a live-set swap and needs no
    resharding collective (guarded by ResidentStack.install_hlo in the
    bench).  MoE expert-parallel leaves are NOT coverable this way (their
    expert dim would collide with the task dim); registry keeps MoE off the
    slotted decode path.
    """
    for kp, leaf in jax.tree_util.tree_leaves_with_path(tree):
        last = _path_str(kp).split("/")[-1]
        if last not in ("scale", "zero"):
            raise ValueError(
                f"stacked scale tree has non-scale leaf {_path_str(kp)!r}")
    return param_specs(tree)


def stacked_scale_shardings(ctx, tree) -> dict:
    """``stacked_scale_specs`` as NamedShardings — what ResidentStack hands
    to ``device_put`` for the stack and for each installed row."""
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                        stacked_scale_specs(tree),
                        is_leaf=lambda x: isinstance(x, P))


def _probe_dims(init_cache, args1, args2):
    """Trace ``init_cache`` at two argument tuples and return the per-leaf
    index of the first differing dim (``-1`` if none).  Abstract tracing
    only — nothing is allocated.  The shared core of the structural dim
    oracles below."""
    c1 = jax.eval_shape(lambda: init_cache(*args1))
    c2 = jax.eval_shape(lambda: init_cache(*args2))

    def diff(a, b):
        return next((i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y), -1)
    return jax.tree.map(diff, c1, c2)


def cache_batch_dims(init_cache, batch: int, seq_len: int = 8):
    """Per-leaf batch-dim index for a cache tree, derived STRUCTURALLY:
    trace ``init_cache`` at two batch sizes and diff the shapes.  Immune to
    extent collisions (batch == n_layers, etc.) that break any
    match-by-extent heuristic; ``-1`` marks leaves with no batch dim."""
    return _probe_dims(init_cache, (batch, seq_len), (batch + 1, seq_len))


def cache_seq_dims(init_cache, batch: int, seq_len: int = 8):
    """Per-leaf sequence(-capacity) dim index for a cache tree, derived
    STRUCTURALLY: trace ``init_cache`` at two sequence lengths and diff the
    shapes (the same trick as ``cache_batch_dims``) — immune to extent
    collisions and to layout families that stack the seq dim at different
    depths.  ``-1`` marks leaves with no seq dim (SSM / recurrent states).

    This is what the engine's cache-growing and slot-admission writes key
    on: the paged slot pool is ``init_cache(n_slots, cache_len)``, so its
    slot dim IS the batch dim (``cache_batch_dims``) and shards over the
    data axes exactly like the lockstep batch did, while prompt KV rows
    land along the dim this function names.

    Sliding-window caches clamp capacity to the window — the two probe
    lengths must straddle the clamp (``seq_len < window``) or the seq dim
    is invisible; callers that know the window pass
    ``seq_len=min(8, window - 1)`` (Engine._cache_dims does)."""
    return _probe_dims(init_cache, (batch, seq_len), (batch, seq_len + 1))


def cache_specs(ctx, cache, batch: int, batch_sharded: bool,
                n_kv_heads: int = 0, batch_dims=None):
    """PartitionSpec tree for KV caches / SSM states.

    Shard the batch dim over the data axes where it divides, AND the
    kv-head dim over the model axis where it divides — without the latter a
    500k-context cache replicates over the model axis and cannot fit HBM
    (batch=1 gives the data axes nothing to shard).

    ``batch_dims`` (from ``cache_batch_dims``) pins the batch dim per leaf
    exactly; callers with an ``init_cache`` at hand should always pass it.
    Without it, the batch dim falls back to the FIRST dim whose extent
    equals the global batch — cache layouts are stacked over layers/groups
    with the batch dim at varying depth per family (attn: (L,B,C,H,D);
    zamba ssm: (G,every,B,…)), so the fallback misfires when the batch
    extent collides with a leading stack extent (e.g. batch == n_layers).
    Shared by the dry-run cost model and the serving engine so the two can
    never disagree on cache layout.
    """
    msize = ctx.model_size

    def spec(l, bdim):
        nd = jnp.ndim(l)
        parts = [None] * nd
        placed_batch = False
        for dim in range(nd):
            is_batch = (dim == bdim) if bdim is not None \
                else (not placed_batch and l.shape[dim] == batch)
            if batch_sharded and not placed_batch and is_batch:
                parts[dim] = ctx.data_axes
                placed_batch = True
            elif (n_kv_heads and dim >= 2 and l.shape[dim] == n_kv_heads
                  and n_kv_heads % msize == 0
                  and ctx.model_axis not in parts):
                parts[dim] = ctx.model_axis
        # kv-heads not model-divisible (GQA kv in {1,4,8}): shard head_dim
        # instead — attention contracts over D, GSPMD psums the partials
        if ctx.model_axis not in parts and nd >= 3 \
                and l.shape[-1] % msize == 0:
            parts[-1] = ctx.model_axis
        return P(*parts)

    if batch_dims is None:
        return jax.tree.map(lambda l: spec(l, None), cache)
    return jax.tree.map(spec, cache, batch_dims)


def validate_for_mesh(tree, mesh) -> List[str]:
    """Check every sharded dim divides its mesh axes; return problem strings
    (empty list == coherent).  Runs on abstract trees — no allocation."""
    sizes = dict(mesh.shape)
    problems: List[str] = []

    def check(kp, leaf):
        path = _path_str(kp)
        spec = spec_for_path(path, len(leaf.shape))
        for dim, ax in enumerate(tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                if a not in sizes:
                    problems.append(f"{path}: axis {a!r} not in mesh "
                                    f"{tuple(mesh.axis_names)}")
                    return
                total *= sizes[a]
            if leaf.shape[dim] % total:
                problems.append(f"{path}: dim {dim} = {leaf.shape[dim]} "
                                f"not divisible by {total} ({ax})")

    jax.tree_util.tree_map_with_path(check, tree)
    return problems
