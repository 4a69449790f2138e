"""Pallas TPU kernel: fused RTN quantize + nibble-pack.

Used at model-conversion time (fp16 checkpoint → PEQA backbone) and by the
int8 gradient-compression path.  One pass per (bn, bk) block: per-group
min/max → (scale, zero) → round/clamp → pack 8 codes/uint32 — the quantized
codes never round-trip through HBM in fp32.

Blocks are group-aligned (``block_k % group_size == 0``) so every group is
fully contained in one block and the reduction is block-local.  Per-channel
mode (group_size = K) uses a single K block per row — fine for d_model-sized
rows; wrappers fall back to the jnp reference for degenerate shapes.

The grid-searched range shrink of ``core.quant.rtn_quantize`` (offline init)
is intentionally NOT in the kernel: the kernel is the high-throughput path
(plain min/max RTN, ``n_grid=1``); calibration runs once, offline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import (PACK, PLANE_PACK, QuantSpec, pack_fields,
                              tile_words)

DEFAULT_BLOCK_N = 64


def _rtn_quantize_block(w, levels: int, group: int):
    """Shared per-block min/max RTN: (bn, bk) f32 → (q codes, scale, zero)."""
    bn, bk = w.shape
    g_blk = bk // group
    wg = w.reshape(bn, g_blk, group)
    lo = jnp.minimum(wg.min(axis=-1), 0.0)
    hi = jnp.maximum(wg.max(axis=-1), 0.0)
    scale = jnp.maximum((hi - lo) / levels, 1e-12)  # (bn, g_blk)
    zero = -lo / scale
    q = jnp.clip(jnp.round(wg / scale[..., None] + zero[..., None]), 0, levels)
    return q.reshape(bn, bk), scale, zero


def _rtn_pack_kernel(w_ref, qw_ref, scale_ref, zero_ref,
                     *, levels: int, group: int, tw: int):
    w = w_ref[...].astype(jnp.float32)              # (bn, bk)
    bn, bk = w.shape
    q, scale, zero = _rtn_quantize_block(w, levels, group)
    qw_ref[...] = pack_fields(q, PACK, 4, tw)
    scale_ref[...] = scale
    zero_ref[...] = zero


def _rtn_pack_planes_kernel(w_ref, qw_ref, scale_ref, zero_ref,
                            *, levels: int, group: int, bits: int, tw: int):
    """Quantize + bit-plane pack: qw block is (bits, bn, bk/32) uint32,
    plane p holding bit ``bits-1-p`` (MSB first) of every code — the codes
    never leave VREGs between round and pack."""
    w = w_ref[...].astype(jnp.float32)              # (bn, bk)
    bn, bk = w.shape
    q, scale, zero = _rtn_quantize_block(w, levels, group)
    q = q.astype(jnp.uint32)
    sel = jnp.arange(bits, dtype=jnp.uint32)[::-1]
    planes = (q[None] >> sel[:, None, None]) & jnp.uint32(1)
    qw_ref[...] = pack_fields(planes, PLANE_PACK, 1, tw)
    scale_ref[...] = scale
    zero_ref[...] = zero


@functools.partial(
    jax.jit, static_argnames=("spec", "block_n", "block_k", "interpret")
)
def rtn_pack_pallas(
    w: jax.Array,                # (N, K) float
    *,
    spec: QuantSpec,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int | None = None,
    interpret: bool = False,
):
    """min/max RTN quantize + pack.  Returns (qw, scale (N, G), zero (N, G));
    ``qw`` is uint32 (N, K/8) nibbles or (bits, N, K/32) bit-planes per
    ``spec.layout``."""
    n, k = w.shape
    group = spec.group_size or k
    bk = block_k or min(max(group, 2048), k)
    bk = (bk // group) * group
    fields = PLANE_PACK if spec.plane else PACK
    tw = tile_words(k, fields)
    if k % bk or bk % (fields * tw):   # blocks hold whole packing tiles
        bk = k
    bn = min(block_n, n)
    g_blk = bk // group

    grid = (pl.cdiv(n, bn), k // bk)
    sz_specs = [
        pl.BlockSpec((bn, g_blk), lambda i, kk: (i, kk)),
        pl.BlockSpec((bn, g_blk), lambda i, kk: (i, kk)),
    ]
    sz_shapes = [
        jax.ShapeDtypeStruct((n, k // group), jnp.float32),
        jax.ShapeDtypeStruct((n, k // group), jnp.float32),
    ]
    if spec.plane:
        bits = spec.bits
        qw, scale, zero = pl.pallas_call(
            functools.partial(_rtn_pack_planes_kernel, levels=spec.levels,
                              group=group, bits=bits, tw=tw),
            grid=grid,
            in_specs=[pl.BlockSpec((bn, bk), lambda i, kk: (i, kk))],
            out_specs=[
                pl.BlockSpec((bits, bn, bk // PLANE_PACK),
                             lambda i, kk: (0, i, kk)),
                *sz_specs,
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bits, n, k // PLANE_PACK), jnp.uint32),
                *sz_shapes,
            ],
            interpret=interpret,
        )(w)
        return qw, scale, zero
    qw, scale, zero = pl.pallas_call(
        functools.partial(_rtn_pack_kernel, levels=spec.levels, group=group,
                          tw=tw),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, bk), lambda i, kk: (i, kk))],
        out_specs=[
            pl.BlockSpec((bn, bk // PACK), lambda i, kk: (i, kk)),
            *sz_specs,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k // PACK), jnp.uint32),
            *sz_shapes,
        ],
        interpret=interpret,
    )(w)
    return qw, scale, zero
