"""Pallas TPU kernels: fused sub-4-bit dequant + matmul (W{3,4}A16 GEMM/GEMV).

This is the paper's deployment-side win (§3.3): weight-only-quantized LLM
layers are memory-bound at generation time; streaming b-bit codes instead of
16-bit weights cuts HBM traffic ~16/b×.  GPU implementations (OPTQ, AWQ,
LUT-GEMM) use CUDA GEMV kernels; the TPU-native adaptation is:

  HBM → VMEM : packed code words (bn, K/8) + per-group scales/zeros (G, bn)
  VMEM → VREG: one shift + mask per field gives a lane-dense (bn, tw) block
               of integer codes (core.quant tile layout — no lane reshape)
  VREG → MXU : the codes, exact in the activation dtype, are contracted
               with the matching activation columns; the per-group affine
               map is applied to the dot's output (below)

Per group ``g`` of columns, with Ŵ = s·(q − z)::

    x·Ŵᵀ  =  Σ_g  s_g · (x_g·q_gᵀ − z_g · Σ_{k∈g} x_k)

so the MXU only ever multiplies small integers (exact in bf16) by the
activations, with f32 accumulation, and the scale/zero epilogue runs on
(M, bn) outputs.  Scales and zeros enter transposed, (G, N), so a group's
scale row is a lane-dense (1, bn) vector that broadcasts over the rows.
With bf16 activations this is one MXU pass at full f32-accumulation
accuracy; f32 activations take the f32 (HIGHEST) contraction.

One kernel body serves three shapes (docs/KERNELS.md):

  * ``quant_matmul_pallas`` — GEMM, grid (M/bm, N/bn).
  * ``quant_gemv_pallas``  — decode-shaped GEMV: a single M block, so the
    whole (M, K) activation block stays VMEM-resident and each packed
    ``qw`` word is streamed from HBM exactly once per call.
  * ``quant_gmm_pallas``   — grouped over experts: rows sorted by expert in
    ``bm``-row tiles, grid (tiles,), each tile against the whole (N, K)
    codes of its expert.  Consecutive tiles of one expert keep the same
    codes block, so Pallas fetches each touched expert's codes once a call
    and never an untouched expert's.

Each grid step holds a whole K row of codes (no K grid axis): a (bn, K/8)
word block is 512 KiB at K = 8192, and the codes are unpacked one
(bn, tw) field at a time, so no (bn, K) tile is ever materialised.

Both take an optional ``task_ids: (M,) int32``; scales/zeros are then
(T, N, G)-stacked and row i of the output reads task ``task_ids[i]``'s
scales — slots decoding different PEQA tasks share one launch, and a
prompt admitted for a resident task prefills without a scale swap.  A row
of the stacked call is bitwise the plain call under that task's scales:
the codes' dot is shared and the epilogue's arithmetic is the same.

A decode layer scan passes the quantized operands layer-STACKED, with the
layer's index: codes ``(L, …)`` uint32 and scales ``(L, [E,] T, N, G)``
as they are stored, plus ``layer`` as a scalar-prefetched operand.  Every
BlockSpec index map then selects block ``layer`` of the leading axis, so
the kernel DMAs that layer's blocks straight out of the stacks: no layer
is sliced out or copied first.  Each operand is read in the order the chip
stores it (``minor_swapped``): codes whose K/8 is no multiple of 128 words
are stored words-major and read as (K/8, bn) blocks, and per-channel
scales (G = 1) as ``(…, 1, N)``.  Such codes stay uint32 up to the
kernel, which bitcasts each loaded word block to int32 (``_int32``).

Two storage layouts share the tile loop:

  * ``nibble`` — 8 codes per uint32 word; 3-bit rides in nibbles, so the
    HBM stream is 4 bits/weight regardless.
  * ``plane``  — codes stored as ``bits`` packed bit-planes (MSB plane
    first, 32 codes/word/plane; core.quant.pack_codes_planes).  A b-bit
    tensor streams exactly b bits/weight, and a ``spec.bits = p`` view of
    a wider buffer loads only the top-p planes (the BlockSpec's plane axis
    is a prefix slice) — a zero-copy view of the top ``spec.bits`` planes
    through the same BlockSpec that reads a full buffer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import PACK, PLANE_PACK, QuantSpec, tile_words

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128


def _rows(ref, g: int, tids, n_tasks: int):
    """Group ``g``'s scale (or zero) row: (1, bn), or (bm, bn) with each
    output row taking its task's row when ``tids`` is given."""
    row = ref[0, g:g + 1, :]
    if tids is None:
        return row
    sel = jnp.broadcast_to(row, (tids.shape[0], row.shape[1]))
    for t in range(1, n_tasks):
        sel = jnp.where(tids == t, ref[t, g:g + 1, :], sel)
    return sel


def _int32(words):
    """A block of code words as int32 (a bitcast of uint32 words)."""
    if words.dtype == jnp.int32:
        return words
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def qmm_tile(x, qw, scale_t, zero_t, tids, *, group: int, plane_bits: int,
             words_major: bool = False):
    """One (bm, bn) output tile of x @ Ŵᵀ in f32.

    ``x``: (bm, K); ``qw``: int32 or uint32 words (bn, K/8), or (bits, bn,
    K/32) planes of which the first ``plane_bits`` are read — or, with
    ``words_major``, the same words transposed, (K/8, bn) or
    (bits, K/32, bn); ``scale_t`` / ``zero_t``: (T, G, bn); ``tids``:
    (bm, 1) int32 or None.  Every operand may be a Pallas ref or a plain
    array — the kernel and the blocked replay oracle
    (ref.quant_gemv_ref) run this same function.
    """
    k = x.shape[1]
    fields, width = (PLANE_PACK, 1) if plane_bits else (PACK, 4)
    tw = tile_words(k, fields)
    piece = math.gcd(group, tw)       # columns that share one group
    mask = (1 << width) - 1
    n_tasks = scale_t.shape[0]
    xdt = x.dtype
    prec = (jax.lax.Precision.HIGHEST if xdt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    if tids is not None:
        tids = tids[...]
    bm = x.shape[0]
    bn = qw.shape[-1] if words_major else qw.shape[-2]

    def cols(a, lo, hi, *plane):
        """Columns lo:hi (along K) of words or codes: (bn, ·) row-major,
        (·, bn) words-major."""
        return a[plane + ((slice(lo, hi), slice(None)) if words_major
                          else (slice(None), slice(lo, hi)))]
    dims = (((1,), (0 if words_major else 1,)), ((), ()))   # contract K

    y = jnp.zeros((bm, bn), jnp.float32)
    acc = jnp.zeros((bm, bn), jnp.float32)
    xs = jnp.zeros((bm, 1), jnp.float32)
    cur = 0
    for t in range(k // (fields * tw)):
        if plane_bits:
            words = [_int32(cols(qw, t * tw, (t + 1) * tw, p))
                     for p in range(plane_bits)]
        else:
            words = _int32(cols(qw, t * tw, (t + 1) * tw))
        for i in range(fields):
            if plane_bits:
                codes = sum(((w >> i) & 1) << (plane_bits - 1 - p)
                            for p, w in enumerate(words))
            else:
                codes = (words >> (width * i)) & mask
            codes = codes.astype(jnp.float32).astype(xdt)
            col = (t * fields + i) * tw
            for a in range(0, tw, piece):
                g = (col + a) // group
                if g != cur:
                    y = y + _rows(scale_t, cur, tids, n_tasks) * (
                        acc - _rows(zero_t, cur, tids, n_tasks) * xs)
                    acc = jnp.zeros_like(acc)
                    xs = jnp.zeros_like(xs)
                    cur = g
                xc = x[:, col + a:col + a + piece]
                acc = acc + jax.lax.dot_general(
                    xc, codes if piece == tw else cols(codes, a, a + piece),
                    dimension_numbers=dims,                       # x @ q.T
                    preferred_element_type=jnp.float32, precision=prec)
                xs = xs + jnp.sum(xc.astype(jnp.float32), axis=1,
                                  keepdims=True)
    return y + _rows(scale_t, cur, tids, n_tasks) * (
        acc - _rows(zero_t, cur, tids, n_tasks) * xs)


def _kernel(*refs, prefetch: int, has_tids: bool, group: int,
            plane_bits: int, words_major: bool):
    refs = refs[prefetch:]        # the layer index: read by the index maps
    if has_tids:
        tid_ref, x_ref, qw_ref, s_ref, z_ref, o_ref = refs
    else:
        (x_ref, qw_ref, s_ref, z_ref, o_ref), tid_ref = refs, None
    y = qmm_tile(x_ref, qw_ref, s_ref, z_ref, tid_ref, group=group,
                 plane_bits=plane_bits, words_major=words_major)
    o_ref[...] = y.astype(o_ref.dtype)


def minor_swapped(shape) -> bool:
    """Whether a TPU stores a 32-bit array of ``shape`` with its two minor
    dims swapped.  XLA lays an (…, R, C) array out in (8, 128) tiles over
    its two minor dims, in the order that pads less, and row-major on a
    tie: codes (…, N, K/8) whose K/8 is no multiple of 128 (starcoder2's
    K = 4608, an expert's K = 1408) are stored words-major, as are
    per-channel (…, N, 1) scales.  A layer-stacked kernel call reads every
    operand in its stored order, which keeps the operand a bitcast of the
    stack (``tests/test_tpu_compile.py`` checks this rule against XLA)."""
    r, c = shape[-2:]
    padded = lambda rows, cols: -(-rows // 8) * 8 * (-(-cols // 128) * 128)
    return padded(c, r) < padded(r, c)


def operands(qw, scale, zero, task_ids, spec: QuantSpec, m: int,
             layer=None):
    """Kernel-side operand layout: int32 words, (T, G, N) scales/zeros,
    (M, 1) task ids.  Shared with the replay oracle.

    With ``layer``, ``qw``/``scale``/``zero`` are layer-stacked and come
    back in their stored order (``minor_swapped``): the codes uint32 (the
    kernel bitcasts its word blocks), transposed words-major where they are
    stored so, and the scales (L, T, G, N).  Scales stored (…, N, G)
    row-major are transposed, so their layer is taken out first and they
    come back as a one-layer (1, T, G, N) stack."""
    if not (spec.packs or spec.plane):
        raise NotImplementedError(
            "the quant_matmul/quant_gemv Pallas kernels read nibble- or "
            f"plane-packed codes; {spec} stores unpacked codes")
    qw = _codes_operand(qw, layer)
    tids = None if task_ids is None else \
        task_ids.astype(jnp.int32).reshape(m, 1)
    st, zt = _scale_operands(scale, zero, task_ids is not None, layer)
    return qw, st, zt, tids


def _codes_operand(qw, layer):
    """Per-layer codes as int32 words; a layer stack as stored."""
    if layer is None:
        return jax.lax.bitcast_convert_type(qw, jnp.int32)
    return jnp.swapaxes(qw, -1, -2) if minor_swapped(qw.shape) else qw


def _scale_operands(scale, zero, stacked_tasks: bool, layer):
    """f32 (…, T, G, N) scales and zeros, a task dim of 1 added when the
    call has no task ids."""
    if not stacked_tasks:
        scale, zero = scale[..., None, :, :], zero[..., None, :, :]
    return tuple(_transposed(a.astype(jnp.float32), layer)
                 for a in (scale, zero))


def _transposed(scale, layer):
    """(…, N, G) scales as (…, G, N): see ``operands``."""
    if layer is not None:
        if not minor_swapped(scale.shape):
            scale = jax.lax.dynamic_index_in_dim(scale, layer, 0,
                                                 keepdims=True)
        elif scale.shape[-1] == 1:    # the stored order: a bitcast
            return scale.reshape(*scale.shape[:-2], 1, scale.shape[-2])
    return jnp.swapaxes(scale, -1, -2)


def _layer_map(index, stacked: bool):
    """``index`` (grid indices → block indices) for an operand with a
    leading layer axis: the layer's block when the operand holds every
    layer, block 0 of a one-layer operand."""
    if stacked:
        return lambda *g: (g[-1][0],) + index(*g)
    return lambda *g: (0,) + index(*g)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_m", "block_n", "out_dtype", "interpret",
                     "name"))
def _qmm_call(x, qw, scale, zero, task_ids, layer, *, spec: QuantSpec,
              block_m: int, block_n: int, out_dtype, interpret: bool,
              name: str):
    m, k = x.shape
    n = qw.shape[-2]
    stacked = layer is not None
    words_major = stacked and minor_swapped(qw.shape)
    scales_whole = stacked and minor_swapped(scale.shape)  # _transposed
    qw, st, zt, tids = operands(qw, scale, zero, task_ids, spec, m, layer)
    n_tasks, g, _ = st.shape[-3:]
    bm, bn = min(block_m, m), min(block_n, n)
    if n % bn and n <= 8 * block_n:
        bn = n      # one whole-width block (MLA's 576-wide W_kva) beats a
        #             ragged edge block
    words = k // (PLANE_PACK if spec.plane else PACK)
    planes = (spec.bits,) if spec.plane else ()
    top = (0,) * len(planes)            # the top spec.bits stored planes
    if words_major:
        qw_block = planes + (words, bn)
        qw_index = lambda i, j, *_: top + (0, j)
    else:
        qw_block = planes + (bn, words)
        qw_index = lambda i, j, *_: top + (j, 0)
    sz_block, sz_index = (n_tasks, g, bn), lambda i, j, *_: (0, 0, j)
    if stacked:
        qw_block, qw_index = (None,) + qw_block, _layer_map(qw_index, True)
        sz_block = (None,) + sz_block
        sz_index = _layer_map(sz_index, scales_whole)
    sz_spec = pl.BlockSpec(sz_block, sz_index)
    in_specs = [pl.BlockSpec((bm, k), lambda i, j, *_: (i, 0)),
                pl.BlockSpec(qw_block, qw_index), sz_spec, sz_spec]
    args = [x, qw, st, zt]
    if tids is not None:
        in_specs.insert(0, pl.BlockSpec((bm, 1), lambda i, j, *_: (i, 0)))
        args.insert(0, tids)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j))
    prefetch = []
    if layer is None:
        grid_kw = dict(grid=grid, in_specs=in_specs, out_specs=out_spec)
    else:
        prefetch = [jnp.reshape(layer, (1,)).astype(jnp.int32)]
        grid_kw = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec))
    return pl.pallas_call(
        functools.partial(_kernel, prefetch=len(prefetch),
                          has_tids=tids is not None, group=k // g,
                          plane_bits=spec.bits if spec.plane else 0,
                          words_major=words_major),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        interpret=interpret,
        name=name,
        **grid_kw,
    )(*prefetch, *args)


def quant_matmul_pallas(
    x: jax.Array,           # (M, K)
    qw: jax.Array,          # (N, K // 8) uint32 — or (bits', N, K // 32)
    scale: jax.Array,       # (N, G) f32 — or (T, N, G) with task_ids
    zero: jax.Array,        # same shape as scale
    *,
    spec: QuantSpec,
    task_ids: jax.Array | None = None,   # (M,) int32 rows into the T stack
    layer: jax.Array | None = None,      # () int32: operands layer-stacked
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ Ŵᵀ with Ŵ = scale · (codes − zero);  returns (M, N).
    Grid (M/bm, N/bn); ``block_m`` must be a multiple of the activation
    dtype's sublane tile (8 for f32, 16 for bf16) when it is below M.
    With ``layer``, ``qw``/``scale``/``zero`` carry a leading layer axis
    and the call reads layer ``layer`` of them in place."""
    return _qmm_call(x, qw, scale, zero, task_ids, layer, spec=spec,
                     block_m=block_m, block_n=block_n, out_dtype=out_dtype,
                     interpret=interpret, name="quant_matmul")


def quant_gemv_pallas(
    x: jax.Array,           # (M, K), M = n_slots (small)
    qw: jax.Array,          # (N, K // 8) uint32 — or (bits', N, K // 32)
    scale: jax.Array,       # (N, G) f32 — or (T, N, G) with task_ids
    zero: jax.Array,        # same shape as scale
    *,
    task_ids: jax.Array | None = None,   # (M,) int32 rows into the T stack
    layer: jax.Array | None = None,      # () int32: operands layer-stacked
    spec: QuantSpec,
    block_n: int = DEFAULT_BLOCK_N,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Decode-shaped y = x @ Ŵᵀ;  grid (N/bn,), activations resident.

    Plane layout (``spec.plane``): ``qw`` is (bits', N, K/32) and only the
    top ``spec.bits`` planes are streamed — with ``bits' > spec.bits`` this
    reads a plane prefix of a wider buffer.

    Layer-stacked (``layer`` given): ``qw`` is (L, N, K/8) or
    (L, bits', N, K/32), ``scale``/``zero`` (L, N, G) or (L, T, N, G), and
    the call is the plain call on layer ``layer`` of each, read in place.
    """
    return _qmm_call(x, qw, scale, zero, task_ids, layer, spec=spec,
                     block_m=x.shape[0], block_n=block_n,
                     out_dtype=out_dtype, interpret=interpret,
                     name="quant_gemv")


class _FlatRows:
    """A (T·G, N) scale/zero block read as (T, G, N) by ``qmm_tile``: the
    tasks' group rows stacked on the sublane dim, with no padding of a
    size-G dim to the sublane tile."""

    def __init__(self, ref, groups: int):
        self.ref, self.g = ref, groups
        self.shape = (ref.shape[0] // groups, groups, ref.shape[1])

    def __getitem__(self, idx):
        t, rows, cols = idx
        return self.ref[t * self.g + rows.start:t * self.g + rows.stop, cols]


def _gmm_kernel(te_ref, used_ref, *refs, prefetch: int, has_tids: bool,
                group: int, groups: int, plane_bits: int, words_major: bool):
    refs = refs[prefetch:]        # the layer index: read by the index maps
    if has_tids:
        tid_ref, x_ref, qw_ref, s_ref, z_ref, o_ref = refs
    else:
        (x_ref, qw_ref, s_ref, z_ref, o_ref), tid_ref = refs, None
    if len(s_ref.shape) == 2:     # (T·G, N): see quant_gmm_pallas
        s_ref, z_ref = _FlatRows(s_ref, groups), _FlatRows(z_ref, groups)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        y = qmm_tile(x_ref, qw_ref, s_ref, z_ref, tid_ref, group=group,
                     plane_bits=plane_bits, words_major=words_major)
        o_ref[...] = y.astype(o_ref.dtype)


# VMEM a grouped call may take: a whole expert's codes and its tasks'
# scale rows, double-buffered (v5e has 128 MiB)
GMM_VMEM_BYTES = 64 * 1024 * 1024


@functools.partial(
    jax.jit, static_argnames=("spec", "block_m", "out_dtype", "interpret"))
def quant_gmm_pallas(
    x: jax.Array,            # (M, K): rows sorted by expert, bm-row tiles
    qw: jax.Array,           # (E, N, K // 8) — or (E, bits', N, K // 32)
    scale: jax.Array,        # (E, N, G) — or (E, T, N, G) with task_ids
    zero: jax.Array,         # same shape as scale
    tile_expert: jax.Array,  # (M // bm,) int32: expert of each tile
    tiles_used: jax.Array,   # () int32: tiles holding rows; the rest skip
    *,
    spec: QuantSpec,
    block_m: int,
    task_ids: jax.Array | None = None,   # (M,) int32 rows into the T stack
    layer: jax.Array | None = None,      # () int32: operands layer-stacked
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Grouped y[i] = x[i] @ Ŵ(tile_expert[i // bm])ᵀ;  grid (M / bm,).

    Tiles past ``tiles_used`` point at the last used tile's expert (no new
    codes are fetched) and compute nothing: their output rows are left
    unwritten, and nothing may read them.

    Layer-stacked (``layer`` given): ``qw``/``scale``/``zero`` carry a
    leading layer axis, (L, E, …), and each tile reads its expert's blocks
    of layer ``layer`` in place; the scales enter (L, E, T, G, N)."""
    m, k = x.shape
    n = qw.shape[-2]
    bm = block_m
    if m % bm:
        raise ValueError(f"{m} rows are not whole {bm}-row tiles")
    at = lambda i, te, nu, *_: (te[i],)
    words_major = layer is not None and minor_swapped(qw.shape)
    qw = _codes_operand(qw, layer)
    if layer is None:
        e = qw.shape[0]
        if task_ids is None:
            scale, zero = scale[:, None], zero[:, None]
        t_, g = scale.shape[1], scale.shape[-1]
        # (E, T, N, G) → (E, T·G, N): a group's scale row is lane-dense
        st = jnp.swapaxes(scale.astype(jnp.float32), -1, -2).reshape(
            e, t_ * g, n)
        zt = jnp.swapaxes(zero.astype(jnp.float32), -1, -2).reshape(
            e, t_ * g, n)
        sz_spec = pl.BlockSpec((None, t_ * g, n),
                               lambda *a: at(*a) + (0, 0))
        qw_at = at
    else:
        scales_whole = minor_swapped(scale.shape)       # _transposed
        st, zt = _scale_operands(scale, zero, task_ids is not None, layer)
        t_, g = st.shape[-3], st.shape[-2]
        sz_spec = pl.BlockSpec(
            (None, None, t_, g, n),
            _layer_map(lambda *a: at(*a) + (0, 0, 0), scales_whole))
        qw_at = _layer_map(at, True)
    lead = (None,) * (1 if layer is None else 2)      # [layer,] expert
    words = k // (PLANE_PACK if spec.plane else PACK)
    planes = (spec.bits,) if spec.plane else ()
    qw_block = lead + planes + ((words, n) if words_major else (n, words))
    qw_spec = pl.BlockSpec(
        qw_block, lambda *a: qw_at(*a) + (0,) * (len(planes) + 2))
    in_specs = [pl.BlockSpec((bm, k), lambda i, *_: (i, 0)),
                qw_spec, sz_spec, sz_spec]
    args = [x, qw, st, zt]
    if task_ids is not None:
        in_specs.insert(0, pl.BlockSpec((bm, 1), lambda i, *_: (i, 0)))
        args.insert(0, task_ids.astype(jnp.int32).reshape(m, 1))
    prefetch = [tile_expert.astype(jnp.int32),
                jnp.reshape(tiles_used, (1,)).astype(jnp.int32)]
    if layer is not None:
        prefetch.append(jnp.reshape(layer, (1,)).astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n), lambda i, *_: (i, 0)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, prefetch=len(prefetch) - 2,
                          has_tids=task_ids is not None, group=k // g,
                          groups=g, plane_bits=spec.bits if spec.plane else 0,
                          words_major=words_major),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=GMM_VMEM_BYTES),
        interpret=interpret,
        name="quant_gmm",
    )(*prefetch, *args)
