"""Shard-local sampling over vocab-sharded logits.

The ``logitshard`` serving variant keeps decode logits (B, V) sharded over
the model axis on the way OUT of ``decode_step`` (see
``MeshContext.logits_sharding``).  Sampling then never materialises the full
vocab row anywhere: each shard reduces its own V/n slice and the shards
agree on a winner with SCALAR collectives — O(B) bytes per step instead of
the O(B·V) all-gather the replicated layout forces.

  * ``shard_argmax`` — local argmax per shard, then a (value, index)
    max-reduce: ``pmax`` the local best values, mask losers to a sentinel,
    ``pmin`` the surviving GLOBAL indices.  Ties resolve to the smallest
    global index — bit-exact with ``jnp.argmax`` over gathered logits
    (which also returns the first maximal index).
  * ``shard_topk`` — local top-k per shard, all-gather the k·n_shards
    scalar candidates (vocab-independent bytes), top-k those.  Candidate
    order is shard-major so cross-shard ties resolve to the smaller global
    index, same as ``jax.lax.top_k`` on gathered logits; equal values
    *within* one shard beyond its local k can permute the tail.
  * ``shard_sample`` — temperature sampling by the Gumbel-max trick:
    argmax(logits/T + g) samples the softmax exactly, and ``g`` is
    generated PER SHARD from ``fold_in(key, global row) ∘ fold_in(global
    vocab index)`` — the noise field is a pure function of (key, row,
    vocab id), NOT of the layout, so any mesh shape (or no mesh) draws
    the identical token stream and the winner reduce stays the O(B)
    scalar collective of ``shard_argmax``.

All are ``shard_map`` factories: build once per (mesh, batch layout), jit
the result.  Outside a mesh they are plain ``jnp`` reductions, so the
engine can call one code path everywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _local_argmax(lg, *, axis, vocab):
    """Inside shard_map: lg is the (B_local, V/n) logit slice."""
    li = jnp.argmax(lg, axis=-1)
    lv = jnp.take_along_axis(lg, li[:, None], axis=-1)[:, 0]
    gi = (li + jax.lax.axis_index(axis) * lg.shape[-1]).astype(jnp.int32)
    vmax = jax.lax.pmax(lv, axis)
    # losers point past the vocab; pmin keeps the first global maximiser
    cand = jnp.where(lv == vmax, gi, jnp.int32(vocab))
    return jax.lax.pmin(cand, axis)


def _local_topk(lg, *, axis, k):
    lv, li = jax.lax.top_k(lg, k)                       # (B, k) local
    gi = (li + jax.lax.axis_index(axis) * lg.shape[-1]).astype(jnp.int32)
    # k scalars per shard — bytes are O(B·k·n), never O(B·V)
    allv = jax.lax.all_gather(lv, axis, axis=1)         # (B, n, k)
    alli = jax.lax.all_gather(gi, axis, axis=1)
    b = lg.shape[0]
    v, pos = jax.lax.top_k(allv.reshape(b, -1), k)
    return v, jnp.take_along_axis(alli.reshape(b, -1), pos, axis=1)


def shard_argmax(ctx, batch: int):
    """Greedy sampler over vocab-sharded logits → (B,) int32 token ids.

    With ``ctx is None`` returns the plain replicated argmax (the same
    callable signature), so the engine never branches at the call site.
    """
    if ctx is None:
        return lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32)
    ba = ctx.batch_axes(batch)

    def sample(lg):
        # the sentinel only has to exceed every real index; the true vocab
        # extent is known here at trace time
        return shard_map(
            partial(_local_argmax, axis=ctx.model_axis, vocab=lg.shape[-1]),
            mesh=ctx.mesh, in_specs=P(ba, ctx.model_axis),
            out_specs=P(ba), check_vma=False)(lg)
    return sample


def shard_argmax_masked(ctx, batch: int, fill: int = 0):
    """Active-mask-aware greedy sampler for the continuously-batched decode
    loop → ``fn(logits (B, V), active (B,) bool) -> (B,) int32``.

    Free / evicted slots still flow through the decode step (the batch
    extent is the FIXED slot-pool size — that is what keeps the loop at one
    compiled shape), but their logits are garbage; the mask pins their
    sample to ``fill`` so the emitted token stream is deterministic and the
    next step's embedding lookup stays in-vocab.  Active slots sample
    exactly as ``shard_argmax`` (shard-local on a mesh: the ``where`` runs
    on the (B,) winner vector AFTER the scalar max-reduce, so no vocab
    gather appears and the collective payload is unchanged).
    """
    base = shard_argmax(ctx, batch)

    def sample(lg, active):
        return jnp.where(active, base(lg), jnp.int32(fill))
    return sample


def _gumbel_field(key, rows, gidx):
    """(len(rows), len(gidx)) standard Gumbel noise; element (b, i) is a
    pure function of (key, rows[b], gidx[i]).  Keying every element on its
    GLOBAL coordinates (not its position in the local slice) is what makes
    the sampled stream invariant to resharding: a shard holding vocab
    columns [s, s+v) draws exactly the columns [s, s+v) of the one logical
    noise field."""
    def elem(r, i):
        k = jax.random.fold_in(jax.random.fold_in(key, r), i)
        u = jax.random.uniform(k, (), jnp.float32,
                               minval=jnp.finfo(jnp.float32).tiny,
                               maxval=1.0)
        return -jnp.log(-jnp.log(u))
    return jax.vmap(lambda r: jax.vmap(lambda i: elem(r, i))(gidx))(rows)


def _axis_tuple(ba):
    if ba is None:
        return ()
    return (ba,) if isinstance(ba, str) else tuple(ba)


def _local_sample(lg, key, *, axis, batch_axes, vocab, temperature):
    """Inside shard_map: perturb the local slice, reduce like argmax."""
    b, v = lg.shape
    start = jax.lax.axis_index(axis) * v
    gidx = start + jnp.arange(v)
    off = jnp.int32(0)                     # global row = shard offset + local
    for a in _axis_tuple(batch_axes):      # axes nest outer→inner
        off = off * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    rows = jnp.arange(b) + off * b
    g = _gumbel_field(key, rows, gidx)
    z = lg.astype(jnp.float32) / temperature + g
    li = jnp.argmax(z, axis=-1)
    lv = jnp.take_along_axis(z, li[:, None], axis=-1)[:, 0]
    gi = (li + start).astype(jnp.int32)
    vmax = jax.lax.pmax(lv, axis)
    cand = jnp.where(lv == vmax, gi, jnp.int32(vocab))
    return jax.lax.pmin(cand, axis)


def shard_sample(ctx, batch: int, temperature: float):
    """Temperature sampler over (possibly vocab-sharded) logits →
    ``fn(logits (B, V), key) -> (B,) int32``.

    Gumbel-max: argmax(logits/T + Gumbel) is an exact softmax(logits/T)
    sample, and it inherits ``shard_argmax``'s O(B)-byte winner reduce —
    no vocab gather, no materialised probability row.  The noise is keyed
    on (key, global row, global vocab index), so the token stream is
    bit-identical across mesh shapes AND to the off-mesh path (the
    reshard-invariance test in tests/test_sharding.py pins this).

    ``temperature <= 0`` degrades to greedy (``shard_argmax``) with the
    same (lg, key) signature, so callers never branch.
    """
    if temperature <= 0:
        base = shard_argmax(ctx, batch)
        return lambda lg, key: base(lg)
    if ctx is None:
        def dense(lg, key):
            b, v = lg.shape
            g = _gumbel_field(key, jnp.arange(b), jnp.arange(v))
            z = lg.astype(jnp.float32) / temperature + g
            return jnp.argmax(z, axis=-1).astype(jnp.int32)
        return dense
    ba = ctx.batch_axes(batch)

    def sample(lg, key):
        return shard_map(
            partial(_local_sample, axis=ctx.model_axis, batch_axes=ba,
                    vocab=lg.shape[-1], temperature=float(temperature)),
            mesh=ctx.mesh,
            in_specs=(P(ba, ctx.model_axis), P()),
            out_specs=P(ba), check_vma=False)(lg, key)
    return sample


# top-p fixed-point resolution: softmax weights are quantized to integers
# in [0, 2^14] so every cross-shard reduction is an INTEGER psum —
# order-free and therefore bit-identical on any mesh shape (float partial
# sums are partition-dependent and would break reshard invariance)
_TOPP_SCALE = 1 << 14


def _topp_keep(z, vocab, p, *, axis=None):
    """Shared top-p nucleus selection over (possibly sharded) scores.

    ``z`` is the local (B, v) slice of logits/T.
    Returns the (B, v) bool keep-mask of the smallest set of
    highest-probability tokens with mass >= p, resolved entirely in integer
    arithmetic:

      1. weights w = round(softmax-numerator · 2^14) per token (global max
         subtracted first — ``pmax`` of per-shard maxima is exact);
      2. a 2^14+1-bin weighted histogram per shard, integer-psum'd, gives
         the global mass above any threshold without sorting across shards
         (the "sorted-cumsum threshold scan", bucketed);
      3. the threshold q* = max{q : mass(w >= q) >= target}; tokens with
         w > q* are all kept, and the remaining mass deficit is covered by
         the first ``n_tie`` threshold-weight tokens in GLOBAL vocab order
         — each shard learns its tie offset from one scalar exchange (an
         all-gather of per-shard tie counts).

    q* >= 1 always: bin 0 carries zero mass, so mass(w >= 1) equals the
    total and the target (= ceil(p·total), clamped to [1, total]) is met.
    Note p -> 1 keeps every token with w >= 1 — tokens below the 2^-14
    quantization floor are dropped even at p = 1.0.
    """
    b, v = z.shape
    if axis is None:
        gmax = jnp.max(z, axis=-1)
        n_shards, my = 1, 0
    else:
        gmax = jax.lax.pmax(jnp.max(z, axis=-1), axis)
        n_shards, my = vocab // v, jax.lax.axis_index(axis)
    w = jnp.round(jnp.exp(z - gmax[:, None]) * _TOPP_SCALE).astype(jnp.int32)
    total = jnp.sum(w, axis=-1)
    hist = jax.vmap(
        lambda wr: jnp.zeros((_TOPP_SCALE + 1,), jnp.int32).at[wr].add(wr))(w)
    cnt_loc = None
    if axis is not None:
        total = jax.lax.psum(total, axis)
        hist = jax.lax.psum(hist, axis)
    tgt = jnp.ceil(p * total.astype(jnp.float32)).astype(jnp.int32)
    tgt = jnp.clip(tgt, 1, total)
    # mass(w >= q) for every threshold q: reversed cumulative histogram
    mass = jnp.cumsum(hist[:, ::-1], axis=1)[:, ::-1]
    qs = jnp.arange(_TOPP_SCALE + 1, dtype=jnp.int32)
    qstar = jnp.max(jnp.where(mass >= tgt[:, None], qs[None], 0), axis=1)
    above = jnp.concatenate(       # mass(w > q*) = mass(w >= q*+1); pad q=max+1
        [mass, jnp.zeros((b, 1), jnp.int32)], axis=1)
    m_gt = jnp.take_along_axis(above, (qstar + 1)[:, None], axis=1)[:, 0]
    need = tgt - m_gt                                   # >= 1 by maximality
    n_tie = (need + qstar - 1) // qstar                 # qstar >= 1, no /0
    is_tie = w == qstar[:, None]
    if axis is None:
        before = jnp.zeros((b,), jnp.int32)
    else:
        cnt = jnp.sum(is_tie, axis=-1).astype(jnp.int32)
        allc = jax.lax.all_gather(cnt, axis, axis=1)    # (B, n) scalars
        before = jnp.sum(
            jnp.where(jnp.arange(n_shards)[None, :] < my, allc, 0), axis=1)
    tie_rank = jnp.cumsum(is_tie, axis=-1).astype(jnp.int32) - is_tie
    return (w > qstar[:, None]) | (
        is_tie & (before[:, None] + tie_rank < n_tie[:, None]))


def _local_top_p(lg, key, *, axis, batch_axes, vocab, p, temperature):
    """Inside shard_map: nucleus-mask the local slice, Gumbel-sample the
    survivors, reduce the winner exactly like ``_local_sample``."""
    b, v = lg.shape
    start = jax.lax.axis_index(axis) * v
    z = lg.astype(jnp.float32) / temperature
    keep = _topp_keep(z, vocab, p, axis=axis)
    gidx = start + jnp.arange(v)
    off = jnp.int32(0)
    for a in _axis_tuple(batch_axes):
        off = off * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    rows = jnp.arange(b) + off * b
    g = _gumbel_field(key, rows, gidx)
    zk = jnp.where(keep, z + g, -jnp.inf)
    li = jnp.argmax(zk, axis=-1)
    lv = jnp.take_along_axis(zk, li[:, None], axis=-1)[:, 0]
    gi = (li + start).astype(jnp.int32)
    vmax = jax.lax.pmax(lv, axis)
    cand = jnp.where(lv == vmax, gi, jnp.int32(vocab))
    return jax.lax.pmin(cand, axis)


def shard_top_p(ctx, batch: int, p: float, temperature: float = 1.0):
    """Top-p (nucleus) sampler over (possibly vocab-sharded) logits →
    ``fn(logits (B, V), key) -> (B,) int32``.

    Shard-local: each shard scans its own slice against the integer
    threshold histogram (one integer psum, vocab-independent bytes) and the
    shards agree on the nucleus boundary with one scalar exchange per shard
    (the tie-count all-gather) — the full vocab row is never gathered.
    Everything cross-shard is integer arithmetic, so the kept set — and,
    through the globally-keyed Gumbel field, the sampled stream — is
    bit-identical across mesh shapes and to the off-mesh path.

    ``temperature <= 0`` degrades to greedy with the same (lg, key)
    signature, exactly like ``shard_sample``.
    """
    if not 0.0 < p <= 1.0:
        raise ValueError(f"top-p needs 0 < p <= 1, got {p}")
    if temperature <= 0:
        base = shard_argmax(ctx, batch)
        return lambda lg, key: base(lg)
    if ctx is None:
        def dense(lg, key):
            b, v = lg.shape
            z = lg.astype(jnp.float32) / temperature
            keep = _topp_keep(z, v, float(p))
            g = _gumbel_field(key, jnp.arange(b), jnp.arange(v))
            zk = jnp.where(keep, z + g, -jnp.inf)
            return jnp.argmax(zk, axis=-1).astype(jnp.int32)
        return dense
    ba = ctx.batch_axes(batch)

    def sample(lg, key):
        return shard_map(
            partial(_local_top_p, axis=ctx.model_axis, batch_axes=ba,
                    vocab=lg.shape[-1], p=float(p),
                    temperature=float(temperature)),
            mesh=ctx.mesh,
            in_specs=(P(ba, ctx.model_axis), P()),
            out_specs=P(ba), check_vma=False)(lg, key)
    return sample


def shard_topk(ctx, batch: int, k: int):
    """Top-k over vocab-sharded logits → ((B, k) values, (B, k) indices)."""
    if ctx is None:
        def dense(lg):
            v, i = jax.lax.top_k(lg, k)
            return v, i.astype(jnp.int32)
        return dense
    ba = ctx.batch_axes(batch)
    return shard_map(
        partial(_local_topk, axis=ctx.model_axis, k=k),
        mesh=ctx.mesh,
        in_specs=P(ba, ctx.model_axis),
        out_specs=(P(ba), P(ba)),
        check_vma=False)
