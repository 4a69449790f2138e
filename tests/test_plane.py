"""Bit-plane packed codes: layout bijectivity, prefix-draft semantics, and
kernel bit-exactness.

The layout contract (core.quant, docs/KERNELS.md "Bit-plane packing"):

  * ``pack_codes_planes(q, b)`` is a bijection — unpack returns ``q``;
  * the top ``p`` planes are the p-bit truncation of the codes:
    ``unpack(qw[:p]) == q >> (b - p)`` — a DRAFT model is a buffer-prefix
    READ of the target's weights, zero extra memory;
  * ``draft_scales`` rescales (s, z) so the truncated codes decode to
    (approximately) the same weights: s·2^(b-p), z/2^(b-p);
  * every kernel path (pallas-interpret, XLA fallback, blocked replay)
    agrees on the plane layout within the f32 summation-order bound, and
    the spec-view where ``spec.bits < qw.shape[0]`` slices the prefix
    in-kernel is bitwise the kernel over the explicitly sliced prefix.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.quant import (PLANE_PACK, QTensor, QuantSpec, draft_scales,
                              pack_codes_planes, unpack_codes_planes)
from repro.kernels import ops, ref
from repro.kernels import quant_matmul as qm
from repro.kernels import rtn_pack as rp
from conftest import assert_order_close

BN = 64  # force multi-block grids at test shapes


def _spec(bits, group):
    return QuantSpec(bits=bits, group_size=group, layout="plane")


def _make(n, k, group, bits, m, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    spec = _spec(bits, group)
    qt = QTensor.quantize(w, spec, n_grid=2)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    return x, qt, spec


# ---------------------------------------------------------------- layout

@pytest.mark.parametrize("bits", [2, 3, 4])
def test_plane_roundtrip_bijective(bits):
    rng = np.random.default_rng(bits)
    q = jnp.asarray(rng.integers(0, 2 ** bits, (5, 7, 96)).astype(np.uint8))
    p = pack_codes_planes(q, bits)
    assert p.shape == (bits, 5, 7, 96 // PLANE_PACK)
    assert p.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_codes_planes(p)),
                                  np.asarray(q))


@pytest.mark.parametrize("bits,draft", [(3, 2), (4, 2), (4, 3), (4, 1)])
def test_plane_prefix_is_truncation(bits, draft):
    """qw[:p] decodes to q >> (b - p): the MSB-first plane order makes the
    p-bit draft a contiguous buffer prefix."""
    rng = np.random.default_rng(10 * bits + draft)
    q = jnp.asarray(rng.integers(0, 2 ** bits, (6, 64)).astype(np.uint8))
    p = pack_codes_planes(q, bits)
    got = unpack_codes_planes(p[:draft])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(q) >> (bits - draft))


def test_draft_scales_decode_identity():
    """s·(q − z) == s_d·(q_p − z_d) whenever the dropped planes are zero —
    and differs by < s·2^(b-p) (the truncation bound) otherwise."""
    bits, draft = 4, 2
    rng = np.random.default_rng(3)
    q = rng.integers(0, 16, (8, 32)).astype(np.float32)
    s = rng.uniform(0.5, 2.0, (8, 1)).astype(np.float32)
    z = rng.uniform(0.0, 15.0, (8, 1)).astype(np.float32)
    sd, zd = draft_scales(jnp.asarray(s), jnp.asarray(z), bits, draft)
    qp = np.floor(q / 4.0)                    # the 2-bit truncation
    full = s * (q - z)
    approx = np.asarray(sd) * (qp - np.asarray(zd))
    np.testing.assert_allclose(approx, s * (qp * 4.0 - z), rtol=1e-6)
    assert np.all(np.abs(full - approx) < s * 4.0)


# ---------------------------------------------------------------- kernels

@pytest.mark.parametrize("group", [32, 64, 128, None])
@pytest.mark.parametrize("bits", [2, 3, 4])
def test_plane_gemv_bitexact_vs_blocked_replay(group, bits):
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
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bits,draft", [(4, 3), (4, 2), (3, 2)])
def test_plane_gemv_draft_prefix_view(bits, draft):
    """A draft spec over the FULL buffer == the same kernel over the
    explicitly-sliced prefix: the in-kernel plane slice is exact."""
    x, qt, spec = _make(96, 256, 64, bits, m=3, seed=7 * bits + draft)
    dspec = QuantSpec(bits=draft, group_size=64, layout="plane")
    sd, zd = draft_scales(qt.scale, qt.zero, bits, draft)
    via_view = qm.quant_gemv_pallas(x, qt.qw, sd, zd, spec=dspec,
                                    block_n=BN, interpret=True)
    via_slice = qm.quant_gemv_pallas(x, qt.qw[:draft], sd, zd, spec=dspec,
                                     block_n=BN, interpret=True)
    np.testing.assert_array_equal(np.asarray(via_view),
                                  np.asarray(via_slice))
    want = ref.quant_gemv_ref(x, qt.qw[:draft], sd, zd, qt.shape, dspec,
                              block_n=BN)
    tol = ref.f32_order_tolerance(x, qt.qw, sd, zd, qt.shape, dspec)
    assert np.all(np.abs(np.asarray(via_view) - np.asarray(want))
                  <= np.asarray(tol))


@pytest.mark.parametrize("group,bits", [(64, 4), (32, 3), (None, 2)])
def test_plane_gemv_tasks_bitexact(group, bits):
    x, qt, spec = _make(96, 256, group, bits, m=4, seed=20 + bits)
    rng = np.random.default_rng(5)
    scales = jnp.asarray(np.stack([
        np.asarray(qt.scale),
        np.asarray(qt.scale) * rng.uniform(
            0.8, 1.2, qt.scale.shape).astype(np.float32)]))
    zeros = jnp.stack([qt.zero, qt.zero])
    tids = jnp.asarray([1, 0, 1, 0], jnp.int32)
    got = qm.quant_gemv_pallas(x, qt.qw, scales, zeros, task_ids=tids,
                               spec=spec, block_n=BN,
                               interpret=True)
    # row i == the plain GEMV under task tids[i]'s scales
    for t in (0, 1):
        rows = np.flatnonzero(np.asarray(tids) == t)
        plain = qm.quant_gemv_pallas(x[rows], qt.qw, scales[t], zeros[t],
                                     spec=spec, block_n=BN,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(got)[rows],
                                      np.asarray(plain))


@pytest.mark.parametrize("bits", [2, 3, 4])
def test_plane_rtn_pack_kernel_matches_ref(bits):
    rng = np.random.default_rng(30 + bits)
    w = jnp.asarray(rng.normal(size=(96, 256)).astype(np.float32) * 0.05)
    spec = _spec(bits, 64)
    qw_k, s_k, z_k = rp.rtn_pack_pallas(w, spec=spec, block_n=BN,
                                        block_k=128, interpret=True)
    # the kernel is plain min/max RTN — compare against the n_grid=1 oracle
    qw_r, s_r, z_r = ref.rtn_pack_ref(w, spec, n_grid=1)
    np.testing.assert_array_equal(np.asarray(qw_k), np.asarray(qw_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), rtol=1e-5)


def test_plane_ops_dispatch_agrees():
    """ops.quant_matmul on a plane QTensor: xla and ref paths bit-agree
    with the interpret kernel for a decode-shaped call."""
    x, qt, spec = _make(96, 256, 64, 3, m=2, seed=42)
    outs = {}
    for impl in ("interpret", "xla", "ref"):
        outs[impl] = np.asarray(ops.quant_matmul(
            x, qt.qw, qt.scale, qt.zero, spec, impl=impl))
    np.testing.assert_allclose(outs["xla"], outs["ref"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(outs["interpret"], outs["ref"], rtol=1e-5,
                               atol=1e-5)


def test_plane_storage_is_b_bits_per_weight():
    """The packed buffer is exactly bits/8 bytes per weight — the claim the
    bytes/token table in docs/KERNELS.md rests on."""
    for bits in (2, 3, 4):
        _, qt, _ = _make(64, 256, 64, bits, m=1, seed=bits)
        assert qt.qw.size * 4 == bits * 64 * 256 // 8
