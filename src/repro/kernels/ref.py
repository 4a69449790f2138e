"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the kernel sweep tests AND the path the CPU
dry-run compiles (Pallas lowers only for TPU/GPU; see DESIGN.md §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import QuantSpec, unpack_codes, unpack_codes_planes


def _codes(qw, m: int, spec: QuantSpec):
    """(n, m) integer codes from the stored (possibly packed) form."""
    if spec.plane:
        return unpack_codes_planes(qw, m, spec.bits)
    return unpack_codes(qw, m) if spec.packs else qw


def dequant_ref(qw, scale, zero, shape, spec: QuantSpec, dtype=jnp.bfloat16):
    """Ŵ = s·(q−z) from (possibly packed) codes. shape = logical (n, m)."""
    n, m = shape
    codes = _codes(qw, m, spec)
    g = scale.shape[-1]
    qg = codes.reshape(n, g, m // g).astype(jnp.float32)
    w = scale[..., None].astype(jnp.float32) * (qg - zero[..., None].astype(jnp.float32))
    return w.reshape(n, m).astype(dtype)


def quant_matmul_ref(x, qw, scale, zero, shape, spec: QuantSpec, out_dtype=None):
    """y = x @ Ŵᵀ ;  x: (..., K), Ŵ: (N, K) stored as codes; → (..., N)."""
    out_dtype = out_dtype or x.dtype
    w = dequant_ref(qw, scale, zero, shape, spec, jnp.float32)
    y = jnp.einsum("...k,nk->...n", x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def quant_matmul_tasks_ref(x, qw, scale_stack, zero_stack, task_ids, shape,
                           spec: QuantSpec, out_dtype=None):
    """Naive mixed-task oracle: y[i] = x[i] @ Ŵ(task_ids[i])ᵀ.

    scale_stack/zero_stack: (T, N, G); task_ids: (M,) rows into the stack.
    Materializes all T dequantized weights — ground truth only.
    """
    out_dtype = out_dtype or x.dtype
    n, k = shape
    w_all = jax.vmap(
        lambda s, z: dequant_ref(qw, s, z, shape, spec, jnp.float32)
    )(scale_stack, zero_stack)                       # (T, N, K)
    y = jnp.einsum("mk,mnk->mn", x.astype(jnp.float32), w_all[task_ids],
                   preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def quant_gemv_ref(x, qw, scale, zero, shape, spec: QuantSpec, *,
                   task_ids=None, block_n=None, out_dtype=None):
    """Blocked REPLAY of quant_gemv_pallas: the same (N-block) tiling
    running the kernel's own tile function (``quant_matmul.qmm_tile``) on
    plain arrays, so any drift in tiling, field order or group epilogue
    shows.  The interpreted kernel may still differ from this eager replay
    in the last bits, because XLA is free to order an f32 dot's sum
    differently inside the interpreter's loop: compare the two with
    ``f32_order_tolerance``, not bit for bit.

    scale/zero: (N, G), or (T, N, G) stacks when task_ids is given.
    """
    from repro.kernels.quant_matmul import DEFAULT_BLOCK_N, operands, qmm_tile

    bn = min(block_n or DEFAULT_BLOCK_N, shape[0])
    qw, st, zt, tids = operands(qw, scale, zero, task_ids, spec, x.shape[0])
    if spec.plane:
        qw = qw[:spec.bits]
    group = shape[1] // st.shape[1]
    cols = []
    for j0 in range(0, shape[0], bn):
        nsl = slice(j0, min(j0 + bn, shape[0]))
        cols.append(qmm_tile(x, qw[..., nsl, :], st[..., nsl], zt[..., nsl],
                             tids, group=group,
                             plane_bits=spec.bits if spec.plane else 0))
    return jnp.concatenate(cols, axis=1).astype(out_dtype or x.dtype)


# Two f32 evaluations of x @ Ŵᵀ that differ only in the order of their
# sums stay within a few units of roundoff of Σ_k |x_k|·|s|·(q_k + |z|),
# the size of the partial sums the kernel's epilogue form s·(x·q − z·Σx)
# accumulates.  64 units (2^-18 relative) leaves room for that; an
# evaluation with bf16 weights or activations errs by ~2^-9 per product,
# which at these test widths is still >10× over this bound
# (tests/test_gemv.py::test_order_tolerance_rejects_bf16_operands).
ORDER_ULPS = 64


def f32_order_tolerance(x, qw, scale, zero, shape, spec: QuantSpec,
                        task_ids=None):
    """Per-output bound (M, N) on the summation-order difference between
    two f32 evaluations of the quantized product (see ORDER_ULPS)."""
    n, k = shape
    codes = _codes(qw, k, spec).astype(jnp.float32)
    g = scale.shape[-1]
    mag = jnp.abs(scale)[..., None] * (codes.reshape(n, g, k // g)
                                       + jnp.abs(zero)[..., None])
    mag = mag.reshape(*scale.shape[:-2], n, k)      # (N, K) or (T, N, K)
    ax = jnp.abs(x.astype(jnp.float32))
    hi = jax.lax.Precision.HIGHEST
    if task_ids is None:
        bound = jnp.einsum("mk,nk->mn", ax, mag, precision=hi)
    else:
        bound = jnp.einsum("mk,mnk->mn", ax, mag[task_ids], precision=hi)
    return ORDER_ULPS * 2.0 ** -24 * bound


def rtn_pack_ref(w, spec: QuantSpec, n_grid: int = 20):
    """Oracle for the fused RTN quantize+pack kernel = core.quant.rtn_quantize."""
    from repro.core.quant import pack_codes, pack_codes_planes, rtn_quantize

    q, s, z = rtn_quantize(w, spec, n_grid=n_grid)
    if spec.plane:
        return pack_codes_planes(q, spec.bits), s, z
    return (pack_codes(q) if spec.packs else q), s, z


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                        scale: float | None = None, offset=None):
    """Reference (GQA-aware) attention.

    q: (B, Sq, Hq, D), k: (B, Sk, Hkv, D), v: (B, Sk, Hkv, Dv).
    Hq % Hkv == 0.
    window: sliding-window size (Mistral/Mixtral SWA) — key j visible to
    query i iff i - window < j <= i (causal).
    offset: absolute position of query 0; key slot j is at absolute position
    j.  Defaults to Sk - Sq (training / prefill: ends aligned).  Decode with
    a KV cache passes offset = pos so unwritten slots (> pos) are masked.
    A (B,)-shaped offset gives every batch row its OWN query position — the
    continuously-batched decode step, where slots sit at different depths.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # (B, Hkv, rep, Sq, Sk)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf.reshape(b, sq, hkv, rep, d), kf)
    if offset is None:
        offset = sk - sq
    offset = jnp.asarray(offset)
    if offset.ndim:                                   # (B,) per-row offsets
        iq = jnp.arange(sq)[None, :, None] + offset[:, None, None]
        jk = jnp.arange(sk)[None, None, :]
        mask = jnp.ones((b, sq, sk), dtype=bool)
    else:
        iq = jnp.arange(sq)[:, None] + offset
        jk = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= jk <= iq
    if window is not None:
        mask &= jk > iq - window
    mask = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, vf)
    return out.reshape(b, sq, hq, vf.shape[-1]).astype(q.dtype)
