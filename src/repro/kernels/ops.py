"""Public kernel ops: backend dispatch + custom VJP.

``quant_matmul`` is the single entry point models use for every quantized
fully-connected layer.  Forward picks an implementation:

  * ``pallas``   — the fused dequant-matmul TPU kernel (quant_matmul.py)
  * ``interpret``— same kernel, interpret mode (CPU correctness testing)
  * ``xla``      — dequantize to activation dtype + einsum; XLA fuses the
                   (convert → sub → mul) chain into the dot operand.  This is
                   the dry-run / CPU path.

Backward is analytic and implementation-independent (the paper's Eq. (2)
gradient): with  y = x·Ŵᵀ,  Ŵ = s·(q − z),

    dx         = dy · Ŵ
    ds[n, g]   = Σ_{k∈g} (dyᵀx)[n, k] · (q − z)[n, k]
    dz[n, g]   = −s[n, g] · Σ_{k∈g} (dyᵀx)[n, k]      (Table 17 ablation only)

The integer codes get no gradient — they are frozen by construction, which is
the heart of PEQA (the optimizer additionally masks everything non-scale).

Every quantized op also takes ``layer``: its ``qw``/``scale``/``zero`` then
carry a leading layer axis and the op computes with layer ``layer`` of
them.  The Pallas kernels read that layer's blocks in place (the decode
layer scan's path: no layer is sliced out or copied); every other impl
takes the layer out with ``at_layer`` and runs its plain path.  With the
Pallas impls a layer-indexed call is forward-only.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quant
from repro.core.quant import QuantSpec, unpack_codes, unpack_codes_planes
from repro.dist import context as dctx
from repro.kernels import ref as _ref

_tls = threading.local()

KNOWN_IMPLS = ("pallas", "interpret", "xla", "ref", "autodiff")

# quant_matmul calls with at most this many rows take the decode-shaped GEMV
# kernel (grid (N/bn, K/bk), whole activation block VMEM-resident) instead of
# the GEMM tiling — M = n_slots at serve time, so this covers every decode
# step and small batch-1 prefills.  Forward-only: the custom VJP's backward
# never dispatches here (training M is large).
GEMV_MAX_M = 32


def _check_impl(impl: str) -> str:
    """Reject unknown impl strings instead of silently taking the XLA path.

    A typo'd ``REPRO_QMM_IMPL=palas`` used to fall through to XLA and make
    every 'kernel' run silently benchmark the wrong code."""
    if impl not in KNOWN_IMPLS:
        raise ValueError(
            f"unknown quant_matmul impl {impl!r} (from the impl= argument or "
            f"REPRO_QMM_IMPL); known: {', '.join(KNOWN_IMPLS)}")
    return impl


@contextlib.contextmanager
def force_impl(impl: str):
    """Override the quant-matmul implementation within a scope.

    Used by MoE blocks: inside jax.shard_map a custom_vjp cannot express the
    varying-manual-axes bookkeeping for replicated scale params, so those
    regions run the 'autodiff' impl (plain expression — JAX's transpose
    machinery inserts the correct psums for invariant inputs)."""
    prev = getattr(_tls, "impl", None)
    _tls.impl = impl
    try:
        yield
    finally:
        _tls.impl = prev


def default_impl() -> str:
    forced = getattr(_tls, "impl", None)
    if forced:
        return forced
    env = os.environ.get("REPRO_QMM_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def at_layer(a: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a layer-stacked array."""
    return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)


def _codes_f32(qw, k, spec: QuantSpec):
    if spec.plane:
        codes = unpack_codes_planes(qw, k, spec.bits)
    else:
        codes = unpack_codes(qw, k) if spec.packs else qw
    return codes.astype(jnp.float32)


def _dequant(qw, scale, zero, k, spec: QuantSpec, dtype):
    n = qw.shape[1] if spec.plane else qw.shape[0]
    g = scale.shape[-1]
    codes = _codes_f32(qw, k, spec).reshape(n, g, k // g)
    w = scale.astype(jnp.float32)[..., None] * (codes - zero.astype(jnp.float32)[..., None])
    return w.reshape(n, k).astype(dtype)


def _kernel_matmul(x2d, qw, scale, zero, task_ids, spec: QuantSpec,
                   interpret: bool, row_parallel: bool = False, layer=None):
    """The Pallas path: GEMV for decode-shaped calls, GEMM otherwise.

    Inside a mesh scope (``dist.context.use_mesh``) the call runs under
    ``shard_map``, because XLA cannot partition a Mosaic kernel.  A
    row-parallel weight (``wo``/``down``, stored K-sharded) is contracted
    where it lies when its word shards hold whole packing tiles
    (``quant.splits_on_tiles``): each model shard multiplies its activation
    columns by its codes and one f32 ``psum`` adds the partial outputs.
    Any other weight is split by output columns from the full activation
    rows: column-parallel weights are stored that way, and a row-parallel
    one whose K split would cut a tile is resharded to it on entry.  A
    layer index enters replicated; a stack's layer axis is never split.
    """
    from repro.kernels import quant_matmul as _qm

    has_tids = task_ids is not None

    def call(x, w, s, z, *rest, out_dtype=None):
        kern = (_qm.quant_gemv_pallas if x.shape[0] <= GEMV_MAX_M
                else _qm.quant_matmul_pallas)
        return kern(x, w, s, z, task_ids=rest[0] if has_tids else None,
                    layer=rest[-1] if layer is not None else None,
                    spec=spec, out_dtype=out_dtype, interpret=interpret)

    args = (x2d, qw, scale, zero) + (task_ids,) * has_tids
    args += () if layer is None else (layer,)
    ctx = dctx.current()
    if ctx is None:
        return call(*args)
    m, parts = ctx.model_axis, ctx.model_size
    rows = ctx.batch_axes(x2d.shape[0])
    qlead = [None] * (qw.ndim - 2)
    slead = [None] * (scale.ndim - 2)
    groups = scale.shape[-1]
    if (row_parallel and quant.splits_on_tiles(x2d.shape[-1], spec, parts)
            and (groups == 1 or groups % parts == 0)):
        sz = P(*slead, None, m if groups > 1 else None)
        specs = (P(rows, m), P(*qlead, None, m), sz, sz)
        specs += (P(rows),) * has_tids + (P(),) * (layer is not None)

        def partial_sums(*a):
            return jax.lax.psum(call(*a, out_dtype=jnp.float32), m)
        y = jax.shard_map(partial_sums, mesh=ctx.mesh,
                          in_specs=specs, out_specs=P(rows, None),
                          check_vma=False)(*args)
        return y.astype(x2d.dtype)
    cols = m if qw.shape[-2] % parts == 0 else None
    sz = P(*slead, cols, None)
    specs = (P(rows, None), P(*qlead, cols, None), sz, sz)
    specs += (P(rows),) * has_tids + (P(),) * (layer is not None)
    return jax.shard_map(call, mesh=ctx.mesh, in_specs=specs,
                         out_specs=P(rows, cols), check_vma=False)(*args)


def _qmm_fwd_impl(x2d, qw, scale, zero, spec: QuantSpec, impl: str,
                  bf16_reduce: bool = False, row_parallel: bool = False):
    k = x2d.shape[-1]
    if impl in ("pallas", "interpret"):
        return _kernel_matmul(x2d, qw, scale, zero, None, spec,
                              impl == "interpret", row_parallel)
    if impl == "ref":
        n = qw.shape[1] if spec.plane else qw.shape[0]
        return _ref.quant_matmul_ref(x2d, qw, scale, zero, (n, k), spec)
    # xla fast path: dequant in activation dtype, let XLA fuse into the dot
    w = _dequant(qw, scale, zero, k, spec, x2d.dtype)
    return jax.lax.dot_general(
        x2d, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=None if bf16_reduce else jnp.float32,
    ).astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _qmm(x2d, qw, scale, zero, spec: QuantSpec, impl: str, bf16_reduce: bool,
         row_parallel: bool):
    return _qmm_fwd_impl(x2d, qw, scale, zero, spec, impl, bf16_reduce,
                         row_parallel)


def _qmm_fwd(x2d, qw, scale, zero, spec, impl, bf16_reduce, row_parallel):
    y = _qmm_fwd_impl(x2d, qw, scale, zero, spec, impl, bf16_reduce,
                      row_parallel)
    return y, (x2d, qw, scale, zero)


def _qmm_bwd(spec, impl, bf16_reduce, row_parallel, res, dy):
    x2d, qw, scale, zero = res
    k = x2d.shape[-1]
    n = qw.shape[1] if spec.plane else qw.shape[0]
    g = scale.shape[-1]
    with jax.named_scope("qmm_bwd.dequant"):
        w = _dequant(qw, scale, zero, k, spec, x2d.dtype)      # (N, K)
    with jax.named_scope("qmm_bwd.dx"):
        dx = jax.lax.dot_general(                               # dy @ W
            dy, w, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x2d.dtype)
    with jax.named_scope("qmm_bwd.dscale"):
        # c = dyᵀ x  (N, K) in f32
        c = jax.lax.dot_general(
            dy.astype(jnp.float32), x2d.astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        codes = _codes_f32(qw, k, spec).reshape(n, g, k // g)
        cg = c.reshape(n, g, k // g)
        zf = zero.astype(jnp.float32)[..., None]
        ds = jnp.sum(cg * (codes - zf), axis=-1).astype(scale.dtype)
        dz = (-scale.astype(jnp.float32)
              * jnp.sum(cg, axis=-1)).astype(zero.dtype)
    return dx, None, ds, dz


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def quant_matmul(
    x: jax.Array,
    qw: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    spec: QuantSpec,
    *,
    impl: Optional[str] = None,
    bf16_reduce: bool = False,
    row_parallel: bool = False,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """y = x @ Ŵᵀ for arbitrary leading batch dims on x.  Differentiable in
    (x, scale, zero); integer codes are frozen.  ``row_parallel`` marks a
    weight stored K-sharded on a mesh (``dist.sharding.ROW_PARALLEL``);
    only the Pallas path reads it (``_kernel_matmul``).  ``layer``: the
    operands are layer-stacked (module docstring)."""
    impl = _check_impl(impl or default_impl())
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)
    if layer is not None and impl in ("pallas", "interpret"):
        y = _kernel_matmul(x2d, qw, scale, zero, None, spec,
                           impl == "interpret", row_parallel, layer)
        return y.reshape(*lead, y.shape[-1])
    if layer is not None:
        qw, scale, zero = (at_layer(a, layer) for a in (qw, scale, zero))
    if impl == "autodiff":
        # plain expression: autodiff handles scale/zero grads; codes frozen
        w = _dequant(qw, scale, zero, k, spec, x2d.dtype)
        y = jax.lax.dot_general(
            x2d, w, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=None if bf16_reduce else jnp.float32,
        ).astype(x2d.dtype)
    else:
        y = _qmm(x2d, qw, scale, zero, spec, impl, bf16_reduce,
                 row_parallel)
    return y.reshape(*lead, y.shape[-1])


def quant_matmul_slotted(
    x: jax.Array,            # (..., K) with prod(leading dims) == M slots
    qw: jax.Array,           # (N, K // 8) packed codes — shared backbone
    scale_stack: jax.Array,  # (T, N, G) per-task scales
    zero_stack: jax.Array,   # (T, N, G)
    task_ids: jax.Array,     # (M,) int32 rows into the task stacks
    spec: QuantSpec,
    *,
    impl: Optional[str] = None,
    bf16_reduce: bool = False,
    row_parallel: bool = False,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Mixed-task y[i] = x[i] @ Ŵ(task_ids[i])ᵀ — forward-only (serving).
    ``layer``: the operands are layer-stacked (module docstring).

    Slot i's output is BITWISE what ``quant_matmul`` yields when the live
    scale set is ``scale_stack[task_ids[i]]``: each backend computes every
    task's result with the plain path's exact expression and keeps the
    matching rows with a select.  The drain-free scheduler's token-for-token
    equality with drain-then-swap rests on this (test_gemv.py pins it).
    No custom VJP: the codes-frozen gradient story stays on quant_matmul.
    """
    impl = _check_impl(impl or default_impl())
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = qw.shape[-2]
    x2d = x.reshape(-1, k)
    if x2d.shape[0] != task_ids.shape[0]:
        raise ValueError(
            f"task_ids has {task_ids.shape[0]} rows for {x2d.shape[0]} slots")
    if impl in ("pallas", "interpret"):
        y = _kernel_matmul(x2d, qw, scale_stack, zero_stack, task_ids, spec,
                           impl == "interpret", row_parallel, layer)
        return y.reshape(*lead, n)
    if layer is not None:
        qw, scale_stack, zero_stack = (
            at_layer(a, layer) for a in (qw, scale_stack, zero_stack))
    if impl == "ref":
        y = _ref.quant_matmul_tasks_ref(
            x2d, qw, scale_stack, zero_stack, task_ids, (n, k), spec)
    else:  # xla / autodiff: per-task plain-path dot + bitwise-exact select
        y = jnp.zeros((x2d.shape[0], n), x2d.dtype)
        for t in range(scale_stack.shape[0]):
            w = _dequant(qw, scale_stack[t], zero_stack[t], k, spec, x2d.dtype)
            yt = jax.lax.dot_general(
                x2d, w, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=None if bf16_reduce else jnp.float32,
            ).astype(x2d.dtype)
            y = jnp.where((task_ids == t)[:, None], yt, y)
    return y.reshape(*lead, n)


def _dequant_experts(qw, scale, zero, k, spec: QuantSpec, dtype):
    """(E, K, N) dequantized expert weights (plain path)."""
    w = jax.vmap(lambda q, s, z: _dequant(q, s, z, k, spec, dtype))(
        qw, scale, zero)
    return jnp.swapaxes(w, 1, 2)


def _gqmm_plain(x2d, qw, scale, zero, group_sizes, spec: QuantSpec):
    """Grouped matmul by ``ragged_dot`` over the dequantized experts: rows
    past the groups' total come out zero."""
    w = _dequant_experts(qw, scale, zero, x2d.shape[-1], spec, x2d.dtype)
    return jax.lax.ragged_dot(x2d, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _gqmm(x2d, qw, scale, zero, group_sizes, tile_expert, tiles_used,
          spec: QuantSpec, block_m: int, interpret: bool):
    from repro.kernels import quant_matmul as _qm
    return _qm.quant_gmm_pallas(x2d, qw, scale, zero, tile_expert,
                                tiles_used, spec=spec, block_m=block_m,
                                interpret=interpret)


def _gqmm_fwd(x2d, qw, scale, zero, group_sizes, tile_expert, tiles_used,
              spec, block_m, interpret):
    y = _gqmm(x2d, qw, scale, zero, group_sizes, tile_expert, tiles_used,
              spec, block_m, interpret)
    return y, (x2d, qw, scale, zero, group_sizes)


def _gqmm_bwd(spec, block_m, interpret, res, dy):
    """The plain path's gradient: codes frozen, as in ``_qmm_bwd``."""
    x2d, qw, scale, zero, group_sizes = res
    _, vjp = jax.vjp(lambda x, s, z: _gqmm_plain(x, qw, s, z, group_sizes,
                                                 spec), x2d, scale, zero)
    dx, ds, dz = vjp(dy)
    return dx, None, ds, dz, None, None, None


_gqmm.defvjp(_gqmm_fwd, _gqmm_bwd)


def grouped_quant_matmul(
    x: jax.Array,            # (M, K) rows sorted by expert, bm-row tiles
    qw: jax.Array,           # (E, N, K // 8) packed codes, one per expert
    scale: jax.Array,        # (E, N, G) — or (E, T, N, G) with task_ids
    zero: jax.Array,
    group_sizes: jax.Array,  # (E,) int32 rows of each expert, padded to bm
    tile_expert: jax.Array,  # (M // bm,) int32 expert of each tile
    tiles_used: jax.Array,   # () int32 tiles that hold rows
    spec: QuantSpec,
    *,
    block_m: int,
    task_ids: Optional[jax.Array] = None,   # (M,) rows into the T stack
    impl: Optional[str] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Expert-grouped y[i] = x[i] @ Ŵ(e(i))ᵀ, where row i belongs to the
    expert whose group holds it (``moe._group``).  Rows past the groups
    are padding: the kernel leaves them unwritten and the plain path
    zeroes them.  With ``task_ids`` (forward only) each row reads its
    task's expert scales, as ``quant_matmul_slotted`` does for one weight.
    Differentiable in (x, scale, zero) without ``task_ids``, and on the
    Pallas impls without ``layer`` (module docstring)."""
    impl = _check_impl(impl or default_impl())
    if layer is not None and impl not in ("pallas", "interpret"):
        qw, scale, zero = (at_layer(a, layer) for a in (qw, scale, zero))
    elif impl in ("pallas", "interpret"):
        interpret = impl == "interpret"
        if task_ids is None and layer is None:
            return _gqmm(x, qw, scale, zero, group_sizes, tile_expert,
                         tiles_used, spec, block_m, interpret)
        from repro.kernels import quant_matmul as _qm
        return _qm.quant_gmm_pallas(
            x, qw, scale, zero, tile_expert, tiles_used, spec=spec,
            block_m=block_m, task_ids=task_ids, layer=layer,
            interpret=interpret)
    if task_ids is None:
        return _gqmm_plain(x, qw, scale, zero, group_sizes, spec)
    y = jnp.zeros((x.shape[0], qw.shape[-2]), x.dtype)
    for t in range(scale.shape[1]):
        yt = _gqmm_plain(x, qw, scale[:, t], zero[:, t], group_sizes, spec)
        y = jnp.where((task_ids == t)[:, None], yt, y)
    return y


def dequantize_op(qw, scale, zero, out_features_k: int, spec: QuantSpec,
                  dtype=jnp.bfloat16):
    """Materialize Ŵ (for export / QAT comparisons)."""
    return _dequant(qw, scale, zero, out_features_k, spec, dtype)


def rtn_pack(w: jax.Array, spec: QuantSpec, *, impl: Optional[str] = None):
    """Fused quantize+pack (min/max RTN). Falls back to jnp off-TPU."""
    impl = _check_impl(impl or default_impl())
    if impl in ("pallas", "interpret"):
        from repro.kernels.rtn_pack import rtn_pack_pallas

        return rtn_pack_pallas(w, spec=spec, interpret=(impl == "interpret"))
    return _ref.rtn_pack_ref(w, spec, n_grid=1)


def attention(q, k, v, *, causal=True, window=None, scale=None, offset=None,
              impl: str = "dense"):
    """Attention entry point (GQA/SWA-aware).

    impl='dense'  — materialized-logits XLA path (baseline)
    impl='chunked'— online-softmax scan over key blocks + flash-style
                    custom-VJP backward (§Perf: removes the S² HBM term);
                    the Pallas flash kernel slots in here on TPU."""
    if impl == "chunked":
        from repro.kernels.chunked_attention import chunked_attention

        return chunked_attention(q, k, v, causal, window, scale, offset)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    scale=scale, offset=offset)
