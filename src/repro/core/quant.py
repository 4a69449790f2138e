"""Round-to-nearest (RTN) uniform asymmetric quantization — the paper's Eq. (1).

For a weight matrix ``W ∈ R^{n×m}`` (n = output channels, m = input features)
and bit-width ``b``::

    q  = clamp(round(W / s) + z, 0, 2**b - 1)     # unsigned integer codes
    W̄  = q - z                                     # the frozen integer matrix
    Ŵ  = s · W̄                                     # dequantized weights

``s, z`` are per-output-channel (``group_size is None``) or per
``(channel, group)`` with groups of ``group_size`` consecutive input features
(Park et al. [49], paper Table 5).  RTN initialization grid-searches a
shrink factor on the (min, max) range to minimize ``‖W − Ŵ‖_F²`` per group,
matching the paper's "s0, z0 initialized to minimize the Frobenius error".

Zero-points are kept in float (z is only ever used *subtracted from* q before
scaling — exactly Eq. (1) — so a float z costs nothing at inference and lets
the grid search hit the true LSQ optimum).

Packing — two layouts:

  * ``nibble`` (legacy): 8 codes per uint32 word, one nibble each.  A 3-bit
    code rides in a 4-bit nibble, so sub-4-bit buys quantization levels but
    NOT decode bytes.
  * ``plane``: codes are stored as ``bits`` packed bit-planes, most
    significant plane first — ``qw[p]`` is a (N, K/32) uint32 array holding
    bit ``bits-1-p`` of every code.  A b-bit tensor streams exactly b
    bits/weight from HBM, and the top-p planes ``qw[:p]`` are, standing
    alone, the p-bit truncation of every code: a low-bit *draft* reads a
    contiguous prefix of the target's buffer — zero extra weight memory.

Both layouts place codes in words by TILES along K (``tile_words``): a tile
is ``tw`` consecutive words of a row, and field ``i`` of word ``w`` in tile
``t`` holds column ``(t·F + i)·tw + w`` (F fields per word: 8 nibbles or 32
bits).  So one field of a tile's words is a run of ``tw`` consecutive
columns — the TPU kernel unpacks it with a shift and a mask into a
lane-dense (N, tw) block, with no reshape across the lane dim.

A shard of ``qw`` cut along its word axis holds a contiguous run of columns
packed exactly as that narrower row would be only when it holds whole tiles
(``splits_on_tiles``).  Code that reads such shards on their own (a K-split
kernel, MoE's ``shard_map``) checks it first.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Number of codes packed per uint32 word (both 3- and 4-bit use nibbles; a
# 3-bit code simply never sets its top nibble bit).
PACK = 8

# Codes per uint32 word per bit-plane (one bit per code per plane).
PLANE_PACK = 32

# TPU vector lane width: a packing tile is this many words when a row's
# word count divides into such tiles.
LANES = 128

# Version of the packed ``qw`` layout, recorded by checkpoints: 1 put codes
# in words in column order (word w held columns F·w … F·w + F − 1); 2 is
# the tile layout above.  Same shapes and dtypes, different columns.
PACKING_VERSION = 2


def tile_words(k: int, fields: int) -> int:
    """Words per packing tile of a K-wide row with ``fields`` codes/word:
    one lane width (128) when the row's K/fields words split into such
    tiles, else the whole row."""
    words = k // fields
    return LANES if words % LANES == 0 else words


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Static description of a quantized tensor layout."""

    bits: int = 4                  # 2..8
    group_size: Optional[int] = None  # None → per-channel (one group = whole row)
    symmetric: bool = False        # paper uses asymmetric (zero-points)
    packed: bool = True            # bit-pack codes into uint32
    layout: str = "nibble"         # nibble (8 codes/word) | plane (bit-planes)
    scale_dtype: jnp.dtype = jnp.float32

    @property
    def levels(self) -> int:
        return (1 << self.bits) - 1

    @property
    def packs(self) -> bool:
        """Nibble packing only holds codes < 16 (bits ≤ 4); wider codes are
        stored unpacked uint8."""
        return self.packed and self.bits <= 4 and self.layout == "nibble"

    @property
    def plane(self) -> bool:
        """Bit-plane packed: ``qw`` is (bits', N, K/32) uint32 with
        ``bits' >= bits`` — decode consumes the top ``bits`` planes."""
        return self.packed and self.layout == "plane"

    def n_groups(self, in_features: int) -> int:
        if self.group_size is None:
            return 1
        if in_features % self.group_size:
            raise ValueError(
                f"in_features={in_features} not divisible by group_size={self.group_size}"
            )
        return in_features // self.group_size

    @property
    def fields(self) -> int:
        """Codes per stored ``qw`` element along K: 32 (plane), 8 (nibble)
        or 1 (unpacked uint8 codes)."""
        return PLANE_PACK if self.plane else PACK if self.packs else 1

    def validate(self, in_features: int) -> None:
        if not (2 <= self.bits <= 8):
            raise ValueError(f"bits must be in [2, 8], got {self.bits}")
        if self.layout not in ("nibble", "plane"):
            raise ValueError(f"unknown layout {self.layout!r} "
                             f"(know: nibble, plane)")
        self.n_groups(in_features)
        if self.packs and in_features % PACK:
            raise ValueError(f"packed layout needs in_features % {PACK} == 0")
        if self.plane and in_features % PLANE_PACK:
            raise ValueError(
                f"plane layout needs in_features % {PLANE_PACK} == 0")


# ---------------------------------------------------------------------------
# Pack / unpack (bijective on codes in [0, 15])
# ---------------------------------------------------------------------------

def pack_fields(q: jax.Array, fields: int, width: int, tw: int) -> jax.Array:
    """Pack codes (…, K) into uint32 words (…, K // fields), tile by tile:
    field ``i`` (bits ``[i·width, (i+1)·width)``) of word ``w`` in tile
    ``t`` holds column ``(t·fields + i)·tw + w``.  ``K`` must be a multiple
    of ``fields·tw``."""
    lead, k = q.shape[:-1], q.shape[-1]
    q = q.astype(jnp.uint32).reshape(*lead, k // (fields * tw), fields, tw)
    shifts = (jnp.arange(fields, dtype=jnp.uint32) * width)[:, None]
    words = jnp.sum(q << shifts, axis=-2, dtype=jnp.uint32)
    return words.reshape(*lead, k // fields)


def unpack_fields(words: jax.Array, fields: int, width: int,
                  tw: int) -> jax.Array:
    """Inverse of ``pack_fields``: uint32 (…, W) → uint32 codes
    (…, W·fields)."""
    lead, w = words.shape[:-1], words.shape[-1]
    t = words.reshape(*lead, w // tw, 1, tw)
    shifts = (jnp.arange(fields, dtype=jnp.uint32) * width)[:, None]
    q = (t >> shifts) & jnp.uint32((1 << width) - 1)
    return q.reshape(*lead, w * fields)


def splits_on_tiles(k: int, spec: QuantSpec, parts: int) -> bool:
    """Whether cutting the word axis of a K-wide ``qw`` into ``parts``
    equal shards gives each shard whole tiles — i.e. shard j is exactly the
    packed form of columns [j·K/parts, (j+1)·K/parts) as ``pack_codes``
    would pack a K/parts-wide row."""
    f = spec.fields
    if (k // f) % parts:
        return False
    return f == 1 or (k // f // parts) % tile_words(k, f) == 0


def local_columns(qw: jax.Array, spec: QuantSpec, index, parts: int):
    """Columns [index·K/parts, (index+1)·K/parts) of whole packed rows
    ``qw``, repacked as that narrower row would be packed.  ``index`` may
    be traced (``lax.axis_index``) — for packed shards that do not split
    on tiles (``splits_on_tiles``)."""
    f = spec.fields
    width = 1 if spec.plane else 4
    k = qw.shape[-1] * f
    kl = k // parts
    cols = unpack_fields(qw, f, width, tile_words(k, f))
    cols = jax.lax.dynamic_slice_in_dim(cols, index * kl, kl, cols.ndim - 1)
    return pack_fields(cols, f, width, tile_words(kl, f))


def pack_codes(q: jax.Array) -> jax.Array:
    """Pack uint codes (…, K) with values < 16 into uint32 (…, K // 8)."""
    k = q.shape[-1]
    if k % PACK:
        raise ValueError(f"last dim {k} not divisible by {PACK}")
    return pack_fields(q, PACK, 4, tile_words(k, PACK))


def unpack_codes(packed: jax.Array, k: Optional[int] = None) -> jax.Array:
    """Unpack uint32 (…, K//8) → uint8 codes (…, K)."""
    kk = packed.shape[-1] * PACK
    q = unpack_fields(packed, PACK, 4, tile_words(kk, PACK))
    if k is not None:
        q = q[..., :k]
    return q.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Bit-plane pack / unpack (plane-major, MSB first: qw[:p] IS the p-bit draft)
# ---------------------------------------------------------------------------

def pack_codes_planes(q: jax.Array, bits: int) -> jax.Array:
    """Pack uint codes (…, K) < 2**bits into uint32 planes (bits, …, K//32).

    Plane p holds bit ``bits-1-p`` of every code (most significant first),
    32 codes per word, placed by ``pack_fields`` tiles.  The layout is
    chosen so the top-p planes are a contiguous buffer prefix AND decode,
    on their own, to ``code >> (bits-p)`` — the p-bit truncation a low-bit
    draft serves under rescaled (scale, zero).
    """
    k = q.shape[-1]
    if k % PLANE_PACK:
        raise ValueError(f"last dim {k} not divisible by {PLANE_PACK}")
    q = q.astype(jnp.uint32)
    # (bits, …, K): bit bits-1-p of each code
    sel = jnp.arange(bits, dtype=jnp.uint32)[::-1]
    sel = sel.reshape((bits,) + (1,) * q.ndim)
    planes = (q[None] >> sel) & jnp.uint32(1)
    return pack_fields(planes, PLANE_PACK, 1, tile_words(k, PLANE_PACK))


def unpack_codes_planes(packed: jax.Array, k: Optional[int] = None,
                        bits: Optional[int] = None) -> jax.Array:
    """Unpack uint32 planes (bits', …, K//32) → uint8 codes (…, K).

    ``bits`` (≤ bits') consumes only the top planes — the draft decode.
    """
    bits = packed.shape[0] if bits is None else bits
    kk = packed.shape[-1] * PLANE_PACK
    b = unpack_fields(packed[:bits], PLANE_PACK, 1,
                      tile_words(kk, PLANE_PACK))
    weight = jnp.arange(bits, dtype=jnp.uint32)[::-1]
    weight = weight.reshape((bits,) + (1,) * (b.ndim - 1))
    q = jnp.sum(b << weight, axis=0, dtype=jnp.uint32)
    if k is not None:
        q = q[..., :k]
    return q.astype(jnp.uint8)


def draft_scales(scale: jax.Array, zero: jax.Array, bits: int,
                 draft_bits: int):
    """(scale, zero) for decoding the top ``draft_bits`` planes of a
    ``bits``-bit tensor.

    The p-bit truncation satisfies ``q ≈ q_p · 2**(b-p)``, so
    ``s·(q − z) ≈ (s·2**(b-p)) · (q_p − z/2**(b-p))`` — the draft reuses
    the target's trained scales, rescaled.  This is the default draft
    scale set; a task may also train dedicated p-bit scales (PEQA's whole
    point) and install them instead.
    """
    f = float(1 << (bits - draft_bits))
    return scale * f, zero / f


# ---------------------------------------------------------------------------
# RTN quantization
# ---------------------------------------------------------------------------

def _grouped(w: jax.Array, spec: QuantSpec) -> jax.Array:
    """(n, m) → (n, G, m/G) view."""
    n, m = w.shape
    g = spec.n_groups(m)
    return w.reshape(n, g, m // g)


def _rtn_params_for_range(wg, lo, hi, spec: QuantSpec):
    """Given per-group (lo, hi), produce (scale, zero) for asymmetric quant."""
    levels = spec.levels
    if spec.symmetric:
        amax = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
        scale = jnp.maximum(amax / ((levels - 1) / 2), 1e-12)
        zero = jnp.full_like(scale, (levels + 1) / 2)  # midpoint code
    else:
        scale = jnp.maximum((hi - lo) / levels, 1e-12)
        zero = -lo / scale  # float zero-point (code of real value 0… of lo)
    return scale, zero


def _quantize_with(wg, scale, zero, spec: QuantSpec):
    q = jnp.clip(jnp.round(wg / scale[..., None] + zero[..., None]), 0, spec.levels)
    return q


def rtn_quantize(
    w: jax.Array,
    spec: QuantSpec,
    *,
    n_grid: int = 20,
    max_shrink: float = 0.45,
):
    """RTN with per-group range grid-search (minimize per-group Frobenius err).

    Returns (q_codes uint8 (n, m), scale (n, G), zero (n, G)).
    ``n_grid=1`` disables the search (plain min/max RTN).
    """
    w = w.astype(jnp.float32)
    wg = _grouped(w, spec)
    lo = jnp.minimum(wg.min(axis=-1), 0.0)
    hi = jnp.maximum(wg.max(axis=-1), 0.0)

    def err_for(shrink):
        s, z = _rtn_params_for_range(wg, lo * shrink, hi * shrink, spec)
        q = _quantize_with(wg, s, z, spec)
        deq = s[..., None] * (q - z[..., None])
        return jnp.sum((deq - wg) ** 2, axis=-1), s, z

    if n_grid <= 1:
        _, scale, zero = err_for(1.0)
    else:
        shrinks = jnp.linspace(1.0, 1.0 - max_shrink, n_grid)

        def body(carry, shrink):
            best_err, best_s, best_z = carry
            e, s, z = err_for(shrink)
            take = e < best_err
            return (
                jnp.where(take, e, best_err),
                jnp.where(take, s, best_s),
                jnp.where(take, z, best_z),
            ), None

        e0, s0, z0 = err_for(1.0)
        (_, scale, zero), _ = jax.lax.scan(body, (e0, s0, z0), shrinks[1:])

    q = _quantize_with(wg, scale, zero, spec).reshape(w.shape).astype(jnp.uint8)
    return q, scale.astype(spec.scale_dtype), zero.astype(spec.scale_dtype)


def dequantize(
    q: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    spec: QuantSpec,
    dtype=jnp.float32,
) -> jax.Array:
    """Ŵ = s · (q − z), per Eq. (1)/(2). q: (n, m) codes; scale/zero: (n, G)."""
    n, m = q.shape
    g = scale.shape[-1]
    qg = q.reshape(n, g, m // g).astype(jnp.float32)
    deq = scale[..., None].astype(jnp.float32) * (qg - zero[..., None].astype(jnp.float32))
    return deq.reshape(n, m).astype(dtype)


# ---------------------------------------------------------------------------
# QTensor — the stored form of one quantized parameter
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Frozen integer weight + (trainable) scale + frozen zero-point.

    ``qw`` is uint32-packed (n, m/8) when ``spec.packed`` else uint8 (n, m).
    ``scale``/``zero`` are (n, G).  ``shape`` is the logical (n, m).
    """

    qw: jax.Array
    scale: jax.Array
    zero: jax.Array
    shape: tuple  # static
    spec: QuantSpec  # static

    def tree_flatten(self):
        return (self.qw, self.scale, self.zero), (self.shape, self.spec)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def codes(self) -> jax.Array:
        if self.spec.plane:
            return unpack_codes_planes(self.qw, self.shape[-1],
                                       self.spec.bits)
        if self.spec.packs:
            return unpack_codes(self.qw, self.shape[-1])
        return self.qw

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return dequantize(self.codes, self.scale, self.zero, self.spec, dtype)

    @classmethod
    def quantize(cls, w: jax.Array, spec: QuantSpec, *, n_grid: int = 20) -> "QTensor":
        spec.validate(w.shape[-1])
        q, s, z = rtn_quantize(w, spec, n_grid=n_grid)
        if spec.plane:
            qw = pack_codes_planes(q, spec.bits)
        elif spec.packs:
            qw = pack_codes(q)
        else:
            qw = q
        return cls(qw=qw, scale=s, zero=z, shape=tuple(w.shape), spec=spec)

    def nbytes_ideal(self) -> int:
        """Deployed size in bytes: b-bit codes + scales + zeros."""
        n, m = self.shape
        code_bits = n * m * self.spec.bits
        meta = self.scale.size + self.zero.size
        return code_bits // 8 + meta * np.dtype(np.float16).itemsize


def quant_error(w: jax.Array, qt: QTensor) -> jax.Array:
    return jnp.sqrt(jnp.mean((qt.dequantize(jnp.float32) - w.astype(jnp.float32)) ** 2))
