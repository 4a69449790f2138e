"""Fused dequant GEMV (decode path): interpret-mode kernels vs oracles.

Three layers of evidence, strongest first:

  * agreement with the blocked-replay oracle (``ref.quant_gemv_ref`` runs
    the kernel's own tile function over the same N blocks) to within
    ``ref.f32_order_tolerance`` — the room two f32 evaluations need when
    XLA orders a dot's sum differently, and far less than bf16 operands
    would cost (``test_order_tolerance_rejects_bf16_operands``).
  * allclose against the naive oracle (full dequant + one einsum) — guards
    the MATH while the replay guards the MECHANICS.
  * the slotted equality contract: rows of the task-stacked GEMV where
    ``task_ids == t`` must be BIT-equal to the plain GEMV under task t's
    scales.  This is what makes the resident scheduler token-for-token
    equal to drain-then-swap (tests/test_serve_mixed_task.py builds on it).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.quant import (QTensor, QuantSpec, draft_scales, pack_codes,
                              pack_codes_planes, tile_words, unpack_codes)
from repro.kernels import ops, ref
from repro.kernels import quant_matmul as qm
from conftest import assert_order_close

BN = 64  # force multi-block grids at test shapes



def _make(n, k, group, bits, m, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    spec = QuantSpec(bits=bits, group_size=group)
    qt = QTensor.quantize(w, spec, n_grid=2)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    return x, qt, spec


def _stacks(qt, n_tasks, seed=1):
    """(T, N, G) scale/zero stacks: task 0 = the base, others perturbed."""
    rng = np.random.default_rng(seed)
    scales = [np.asarray(qt.scale)]
    zeros = [np.asarray(qt.zero)]
    for _ in range(n_tasks - 1):
        scales.append(scales[0] * rng.uniform(0.8, 1.2,
                                              scales[0].shape).astype(
                                                  scales[0].dtype))
        zeros.append(zeros[0])
    return jnp.asarray(np.stack(scales)), jnp.asarray(np.stack(zeros))


@pytest.mark.parametrize("group", [32, 64, 128, None])
@pytest.mark.parametrize("bits", [3, 4])
def test_gemv_bitexact_vs_blocked_replay(group, bits):
    # n=96 does not divide block_n=64 (padded edge tile); k=256 spans
    # several groups for every group choice
    x, qt, spec = _make(96, 256, group, bits, m=4, seed=bits)
    got = qm.quant_gemv_pallas(x, qt.qw, qt.scale, qt.zero, spec=spec,
                               block_n=BN, interpret=True)
    want = ref.quant_gemv_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec,
                              block_n=BN)
    assert_order_close(got, want, x, qt, spec)
    naive = ref.quant_matmul_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(naive),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("group,bits", [(64, 4), (32, 3), (None, 4)])
def test_gemv_tasks_bitexact_vs_blocked_replay(group, bits):
    x, qt, spec = _make(96, 256, group, bits, m=5, seed=7)
    scale_s, zero_s = _stacks(qt, 3)
    tids = jnp.asarray([0, 1, 2, 0, 1], jnp.int32)   # >= 3 distinct tasks
    got = qm.quant_gemv_pallas(x, qt.qw, scale_s, zero_s, task_ids=tids,
                               spec=spec, block_n=BN, interpret=True)
    want = ref.quant_gemv_ref(x, qt.qw, scale_s, zero_s, qt.shape, spec,
                              task_ids=tids, block_n=BN)
    assert_order_close(got, want, x, qt, spec, scale_s, zero_s, tids)
    naive = ref.quant_matmul_tasks_ref(x, qt.qw, scale_s, zero_s, tids,
                                       qt.shape, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(naive),
                               rtol=1e-5, atol=1e-4)


def test_gemv_tasks_rows_equal_plain_per_task():
    """The scheduler-equality contract: row i of the stacked GEMV ==
    the SAME row of the plain GEMV run wholly under task tids[i]."""
    x, qt, spec = _make(64, 256, 64, 4, m=6, seed=3)
    scale_s, zero_s = _stacks(qt, 3)
    tids = np.asarray([0, 1, 2, 2, 1, 0], np.int32)
    got = np.asarray(qm.quant_gemv_pallas(
        x, qt.qw, scale_s, zero_s, task_ids=jnp.asarray(tids), spec=spec,
        block_n=BN, interpret=True))
    for t in range(3):
        plain = np.asarray(qm.quant_gemv_pallas(
            x, qt.qw, scale_s[t], zero_s[t], spec=spec,
            block_n=BN, interpret=True))
        rows = tids == t
        np.testing.assert_array_equal(got[rows], plain[rows])


def test_slotted_xla_rows_equal_plain_xla_per_task():
    """Same contract on the XLA fallback impl (what CPU serving runs)."""
    x, qt, spec = _make(64, 256, 64, 4, m=6, seed=5)
    scale_s, zero_s = _stacks(qt, 3)
    tids = np.asarray([0, 1, 2, 2, 1, 0], np.int32)
    got = np.asarray(ops.quant_matmul_slotted(
        x, qt.qw, scale_s, zero_s, jnp.asarray(tids), spec, impl="xla"))
    for t in range(3):
        plain = np.asarray(ops.quant_matmul(
            x, qt.qw, scale_s[t], zero_s[t], spec, impl="xla"))
        rows = tids == t
        np.testing.assert_array_equal(got[rows], plain[rows])


def test_gemv_dispatch_threshold(monkeypatch):
    """ops.quant_matmul routes decode-shaped calls (m <= GEMV_MAX_M) to the
    GEMV kernel and large-m calls to the GEMM kernel."""
    calls = []
    orig = qm.quant_gemv_pallas

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return orig(*a, **kw)
    monkeypatch.setattr(qm, "quant_gemv_pallas", spy)

    x, qt, spec = _make(64, 256, 64, 4, m=4, seed=9)
    ops.quant_matmul(x, qt.qw, qt.scale, qt.zero, spec, impl="interpret")
    assert calls == [(4, 256)]

    big = jnp.tile(x, (ops.GEMV_MAX_M // 4 + 1, 1))
    assert big.shape[0] > ops.GEMV_MAX_M
    ops.quant_matmul(big, qt.qw, qt.scale, qt.zero, spec, impl="interpret")
    assert calls == [(4, 256)]                       # GEMM path: no new call


def test_unknown_impl_raises(monkeypatch):
    """Regression: a typo'd impl must raise, never silently fall back to
    the XLA path (REPRO_QMM_IMPL=palas used to serve wrong-codepath runs)."""
    x, qt, spec = _make(32, 64, 32, 4, m=2, seed=11)
    with pytest.raises(ValueError, match="palas"):
        ops.quant_matmul(x, qt.qw, qt.scale, qt.zero, spec, impl="palas")
    monkeypatch.setenv("REPRO_QMM_IMPL", "palas")
    with pytest.raises(ValueError, match="REPRO_QMM_IMPL"):
        ops.quant_matmul(x, qt.qw, qt.scale, qt.zero, spec)
    with pytest.raises(ValueError, match="palas"):
        ops.rtn_pack(jnp.zeros((8, 64), jnp.float32), spec)


@pytest.mark.parametrize("k,fields,tw", [
    (2048, 8, 128), (8192, 8, 128), (256, 8, 32), (768, 8, 96),
    (8192, 32, 128), (2048, 32, 64)])
def test_tile_words(k, fields, tw):
    """A packing tile is one 128-word lane width when the row splits into
    such tiles, else the whole row; pack/unpack round-trip either way."""
    assert tile_words(k, fields) == tw
    rng = np.random.default_rng(k + fields)
    q = jnp.asarray(rng.integers(0, 16, (3, k)).astype(np.uint8))
    if fields == 8:
        np.testing.assert_array_equal(
            np.asarray(unpack_codes(pack_codes(q))), np.asarray(q))
    # field i of tile t's words is the run of tw columns at (t·F + i)·tw
    words = np.asarray(pack_codes(q) if fields == 8 else
                       pack_codes_planes(q & 1, 1)[0])
    t, i = k // (fields * tw) - 1, fields - 1
    width = 4 if fields == 8 else 1
    field = (words[:, t * tw:(t + 1) * tw] >> (width * i)) & ((1 << width) - 1)
    col = (t * fields + i) * tw
    want = np.asarray(q)[:, col:col + tw] & ((1 << width) - 1)
    np.testing.assert_array_equal(field, want)


def test_order_tolerance_rejects_bf16_operands():
    """The stated tolerance is tight enough to catch a kernel that rounds
    its dequantized weights or its activations to bf16."""
    x, qt, spec = _make(96, 256, 64, 4, m=4, seed=21)
    exact = ref.quant_matmul_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec)
    tol = np.asarray(ref.f32_order_tolerance(x, qt.qw, qt.scale, qt.zero,
                                             qt.shape, spec))
    w = ref.dequant_ref(qt.qw, qt.scale, qt.zero, qt.shape, spec,
                        jnp.bfloat16).astype(jnp.float32)
    for xx, ww in ((x, w), (x.astype(jnp.bfloat16).astype(jnp.float32),
                            w)):
        bf16 = np.asarray(jnp.einsum("mk,nk->mn", xx, ww,
                                     precision="highest"))
        err = np.abs(bf16 - np.asarray(exact))
        assert np.max(err / tol) > 10.0


def test_gemv_odd_k_blocks_vmem_regression():
    """k=768 packs into one 96-word tile whose 96-column fields straddle
    the 128-column groups: the kernel splits each field at group edges and
    must agree with the replay."""
    x, qt, spec = _make(64, 768, 128, 4, m=3, seed=13)
    got = qm.quant_gemv_pallas(x, qt.qw, qt.scale, qt.zero, spec=spec,
                               interpret=True)     # default blocks
    assert tile_words(768, qm.PACK) == 96
    want = ref.quant_gemv_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec)
    assert_order_close(got, want, x, qt, spec)
    naive = ref.quant_matmul_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(naive),
                               rtol=1e-5, atol=1e-4)


def _layer_stack(n, k, spec, layers=3, n_tasks=0, planes=None, seed=31):
    """Codes, scale and zero stacks of ``layers`` quantized layers (L, …):
    scales (L, T, N, G) with ``n_tasks``; plane codes stored with
    ``planes`` planes (the target's) read under ``spec`` (a draft)."""
    rng = np.random.default_rng(seed)
    store = QuantSpec(bits=planes or spec.bits, group_size=spec.group_size,
                      layout=spec.layout)
    qts = [QTensor.quantize(jnp.asarray(rng.normal(size=(n, k)).astype(
        np.float32) * 0.05), store, n_grid=2) for _ in range(layers)]
    qw = jnp.stack([q.qw for q in qts])
    scale = jnp.stack([q.scale for q in qts])
    zero = jnp.stack([q.zero for q in qts])
    if planes:
        scale, zero = draft_scales(scale, zero, planes, spec.bits)
    if n_tasks:
        mult = rng.uniform(0.8, 1.2, (layers, n_tasks) + scale.shape[1:])
        scale = scale[:, None] * jnp.asarray(mult, jnp.float32)
        zero = jnp.broadcast_to(zero[:, None], scale.shape)
    return qw, scale, zero


# (layout, bits, stored planes): nibbles, bit planes, and the draft's
# view of the top 3 of 4 stored planes
LAYOUTS = {"nibble": ("nibble", 4, None), "plane": ("plane", 3, None),
           "draft": ("plane", 3, 4)}


@pytest.mark.parametrize("n_tasks", [0, 3])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_layer_indexed_gemv_equals_per_layer_call(layout, n_tasks):
    """The decode scan's call — the whole layer stacks and a layer index —
    is bitwise the plain call on that layer, for the first, a middle and
    the last layer.  N = 128 and 128 words a row: stored row-major, the
    order the plain call reads (quant_matmul.minor_swapped)."""
    kind, bits, planes = LAYOUTS[layout]
    spec = QuantSpec(bits=bits, layout=kind)
    k = 1024 if kind == "nibble" else 4096
    qw, scale, zero = _layer_stack(128, k, spec, n_tasks=n_tasks,
                                   planes=planes)
    assert not qm.minor_swapped(qw.shape)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(6, k)).astype(np.float32))
    tids = jnp.asarray([0, 1, 2, 2, 1, 0], jnp.int32) if n_tasks else None
    for layer in (0, 1, 2):
        got = qm.quant_gemv_pallas(x, qw, scale, zero, task_ids=tids,
                                   layer=jnp.int32(layer), spec=spec,
                                   block_n=BN, interpret=True)
        want = qm.quant_gemv_pallas(x, qw[layer], scale[layer],
                                    zero[layer], task_ids=tids, spec=spec,
                                    block_n=BN, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_tasks", [0, 3])
@pytest.mark.parametrize("group", [None, 64])
def test_layer_indexed_gemv_reads_words_major_codes(group, n_tasks):
    """Codes stored words-major (96 outputs of 32 words: each layer
    (K/8, N) on the chip) are read in that order: the same products,
    summed in another order, within the f32 summation-order bound of the
    plain call on the layer."""
    spec = QuantSpec(bits=4, group_size=group)
    qw, scale, zero = _layer_stack(96, 256, spec, n_tasks=n_tasks)
    assert qm.minor_swapped(qw.shape)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(5, 256)).astype(np.float32))
    tids = jnp.asarray([0, 1, 2, 0, 1], jnp.int32) if n_tasks else None
    for layer in (0, 1, 2):
        got = qm.quant_gemv_pallas(x, qw, scale, zero, task_ids=tids,
                                   layer=jnp.int32(layer), spec=spec,
                                   block_n=BN, interpret=True)
        want = qm.quant_gemv_pallas(x, qw[layer], scale[layer],
                                    zero[layer], task_ids=tids, spec=spec,
                                    block_n=BN, interpret=True)
        qt = QTensor(qw[layer], scale[layer], zero[layer], (96, 256), spec)
        assert_order_close(got, want, x, qt, spec, task_ids=tids)


@pytest.mark.parametrize("slotted", [False, True])
@pytest.mark.parametrize("impl", ["interpret", "xla", "ref", "autodiff"])
def test_layer_indexed_ops_equal_per_layer_call(impl, slotted):
    """``ops.quant_matmul`` and ``ops.quant_matmul_slotted`` with
    ``layer=``: one convention in every impl, bitwise the impl's call on
    the layer's own arrays."""
    spec = QuantSpec(bits=4)
    qw, scale, zero = _layer_stack(128, 1024, spec,
                                   n_tasks=3 if slotted else 0)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 3, 1024)).astype(np.float32))
    tids = jnp.asarray([0, 1, 2, 2, 1, 0], jnp.int32)
    if slotted:
        op = lambda *a, **kw: ops.quant_matmul_slotted(
            x, *a, tids, spec, impl=impl, **kw)
    else:
        op = lambda *a, **kw: ops.quant_matmul(x, *a, spec, impl=impl, **kw)
    for layer in (0, 1, 2):
        got = op(qw, scale, zero, layer=jnp.int32(layer))
        want = op(qw[layer], scale[layer], zero[layer])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
