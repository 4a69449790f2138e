"""Per-kernel sweeps: Pallas (interpret mode) vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QTensor, QuantSpec
from repro.kernels import ops, ref
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.rtn_pack import rtn_pack_pallas


SHAPES = [
    # (m, n, k, group, bits)
    (8, 64, 128, None, 4),
    (1, 128, 256, None, 4),     # GEMV (decode)
    (32, 96, 512, 128, 4),
    (16, 64, 256, 64, 3),
    (4, 32, 64, 32, 3),
    (64, 128, 1024, 256, 4),
]


@pytest.mark.parametrize("m,n,k,group,bits", SHAPES)
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_kernel_vs_ref(m, n, k, group, bits, xdtype):
    rng = np.random.default_rng(hash((m, n, k, bits)) % 2 ** 31)
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    spec = QuantSpec(bits=bits, group_size=group)
    qt = QTensor.quantize(w, spec, n_grid=4)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(xdtype)
    y_ref = ref.quant_matmul_ref(x.astype(jnp.float32), qt.qw, qt.scale,
                                 qt.zero, qt.shape, spec)
    y_ker = quant_matmul_pallas(x.astype(jnp.float32), qt.qw, qt.scale,
                                qt.zero, spec=spec, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("block_m,block_n",
                         [(8, 32), (128, 128), (16, 64)])
def test_quant_matmul_block_shape_invariance(block_m, block_n):
    rng = np.random.default_rng(7)
    n, k = 96, 256
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.1)
    spec = QuantSpec(bits=4, group_size=64)
    qt = QTensor.quantize(w, spec)
    x = jnp.asarray(rng.normal(size=(24, k)).astype(np.float32))
    y_ref = ref.quant_matmul_ref(x, qt.qw, qt.scale, qt.zero, qt.shape, spec)
    y = quant_matmul_pallas(x, qt.qw, qt.scale, qt.zero, spec=spec,
                            block_m=block_m, block_n=block_n,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,k,group", [(32, 128, None), (64, 256, 64),
                                       (16, 2048, 512)])
def test_rtn_pack_kernel_vs_ref(n, k, group):
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    spec = QuantSpec(bits=4, group_size=group)
    qw_k, s_k, z_k = rtn_pack_pallas(w, spec=spec, interpret=True)
    qw_r, s_r, z_r = ref.rtn_pack_ref(w, spec, n_grid=1)
    np.testing.assert_array_equal(np.asarray(qw_k), np.asarray(qw_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), rtol=1e-5,
                               atol=1e-5)


def test_custom_vjp_matches_autodiff():
    """ops.quant_matmul grads (dx, ds, dz) == autodiff through dequant."""
    rng = np.random.default_rng(3)
    n, k, g = 48, 128, 32
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    spec = QuantSpec(bits=4, group_size=g)
    qt = QTensor.quantize(w, spec)
    x = jnp.asarray(rng.normal(size=(6, k)).astype(np.float32))
    dy = jnp.asarray(rng.normal(size=(6, n)).astype(np.float32))

    def f_custom(x, s, z):
        return jnp.sum(ops.quant_matmul(x, qt.qw, s, z, spec, impl="xla") * dy)

    def f_auto(x, s, z):
        return jnp.sum(ops.quant_matmul(x, qt.qw, s, z, spec,
                                        impl="autodiff") * dy)

    g1 = jax.grad(f_custom, argnums=(0, 1, 2))(x, qt.scale, qt.zero)
    g2 = jax.grad(f_auto, argnums=(0, 1, 2))(x, qt.scale, qt.zero)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_attention_ref_swa_mask():
    """Sliding window: token attends to at most `window` keys."""
    b, s, h, d = 1, 8, 2, 4
    q = jnp.ones((b, s, h, d))
    k = jnp.ones((b, s, h, d))
    v = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * jnp.ones((b, s, h, d))
    out_full = ref.flash_attention_ref(q, k, v, causal=True)
    out_win = ref.flash_attention_ref(q, k, v, causal=True, window=2)
    # with window=2 the last token averages keys {6, 7} → 6.5
    np.testing.assert_allclose(np.asarray(out_win[0, -1, 0, 0]), 6.5, rtol=1e-5)
    # full causal averages all 8 → 3.5
    np.testing.assert_allclose(np.asarray(out_full[0, -1, 0, 0]), 3.5, rtol=1e-5)


def test_attention_ref_decode_offset():
    """offset masks unwritten cache slots (> pos)."""
    b, sk, h, d = 1, 8, 1, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, sk, h, d)).astype(np.float32))
    out_pos3 = ref.flash_attention_ref(q, k, v, causal=True, offset=3)
    # equivalent: manually truncate the cache to 4 entries
    out_trunc = ref.flash_attention_ref(q, k[:, :4], v[:, :4], causal=True,
                                        offset=3)
    np.testing.assert_allclose(np.asarray(out_pos3), np.asarray(out_trunc),
                               rtol=1e-5, atol=1e-6)


def test_gqa_matches_repeated_mha():
    rng = np.random.default_rng(5)
    b, s, hq, hkv, d = 2, 6, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype(np.float32))
    out = ref.flash_attention_ref(q, k, v)
    krep = jnp.repeat(k, hq // hkv, axis=2)
    vrep = jnp.repeat(v, hq // hkv, axis=2)
    # repeat_interleave ordering: head i uses kv head i // rep.
    # our reshape groups q heads as (hkv, rep) → q head order is interleaved
    q_regrouped = q.reshape(b, s, hkv, hq // hkv, d).reshape(b, s, hq, d)
    out_mha = ref.flash_attention_ref(q_regrouped, krep, vrep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_mha),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "b,sq,sk,h,d,causal,window,offset,bq,bk",
    [(2, 32, 32, 2, 16, True, None, None, 16, 16),
     (1, 8, 24, 4, 8, True, None, 16, 8, 8),      # decode offset
     (2, 32, 32, 2, 16, True, 12, None, 8, 16),   # sliding window
     (1, 16, 48, 2, 8, False, None, None, 16, 12)])
def test_flash_pallas_matches_ref(b, sq, sk, h, d, causal, window, offset,
                                  bq, bk):
    from repro.kernels.flash_attention import flash_attention_pallas
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, sk, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, sk, d)).astype(np.float32))
    o_ref = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        offset=offset).transpose(0, 2, 1, 3)
    o_pal = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                   offset=offset, block_q=bq, block_k=bk,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


def _expert_stacks(k, spec, n_tasks, layers=3, e=4, n=128, seed=0):
    """(L, E, …) codes and scale stacks of ``layers`` MoE layers, the
    scales (L, E, T, N, G) with ``n_tasks``."""
    from repro.core import quant
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 1 << spec.bits, (layers * e, n, k))
    pack = (jax.vmap(lambda q: quant.pack_codes_planes(q, spec.bits))
            if spec.plane else jax.vmap(quant.pack_codes))
    qw = pack(jnp.asarray(codes, jnp.uint32))
    qw = qw.reshape((layers, e) + qw.shape[1:])
    g = spec.n_groups(k)
    shape = (layers, e) + ((n_tasks,) if n_tasks else ()) + (n, g)
    scale = jnp.asarray(rng.uniform(0.01, 0.02, shape), jnp.float32)
    zero = jnp.asarray(rng.uniform(3, 5, shape), jnp.float32)
    return qw, scale, zero


def _tiles(sizes, bm, m):
    ends = np.cumsum(sizes)
    return jnp.asarray(np.minimum(np.searchsorted(
        ends, np.minimum(np.arange(m // bm) * bm, ends[-1] - 1),
        side="right"), len(sizes) - 1).astype(np.int32))


@pytest.mark.parametrize("k", [1024, 256], ids=["row_major", "words_major"])
@pytest.mark.parametrize("layout,group,n_tasks", [
    ("nibble", None, 0), ("nibble", 32, 3), ("plane", None, 2)])
def test_layer_indexed_gmm_equals_per_layer_call(layout, group, n_tasks, k):
    """The grouped expert kernel on the whole (L, E, …) stacks and a layer
    index against the same call on that layer's (E, …) arrays, for the
    first, a middle and the last layer: bitwise where the codes are stored
    row-major (128 outputs of 128 words), and to f32 summation order where
    they are stored words-major (32 words a row) and read so."""
    from repro.kernels import quant_matmul as qm
    spec = QuantSpec(bits=3 if layout == "plane" else 4, group_size=group,
                     layout=layout)
    k = k * 4 if layout == "plane" else k       # the same words a row
    qw, scale, zero = _expert_stacks(k, spec, n_tasks)
    bm, sizes = 16, np.array([32, 0, 16, 48], np.int32)   # expert 1 idle
    rows = int(sizes.sum())
    m = rows + 2 * bm                                      # 2 unused tiles
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    tids = jnp.asarray(rng.integers(0, max(n_tasks, 1), m), jnp.int32) \
        if n_tasks else None
    call = lambda *a, **kw: qm.quant_gmm_pallas(
        x, *a, _tiles(sizes, bm, m), jnp.int32(rows // bm), spec=spec,
        block_m=bm, task_ids=tids, interpret=True, **kw)
    for layer in (0, 1, 2):
        got = np.asarray(call(qw, scale, zero, layer=jnp.int32(layer)))
        want = np.asarray(call(qw[layer], scale[layer], zero[layer]))
        if qm.minor_swapped(qw.shape):
            np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5,
                                       atol=1e-5)
        else:
            np.testing.assert_array_equal(got[:rows], want[:rows])


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_layer_indexed_grouped_op_equals_per_layer_call(impl):
    """``ops.grouped_quant_matmul`` with ``layer=``: bitwise the impl's call
    on the layer's own arrays, with and without task ids."""
    spec = QuantSpec(bits=4)
    bm, sizes = 16, np.array([16, 32, 0, 16], np.int32)
    rows = int(sizes.sum())
    m = rows + bm
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((m, 1024)), jnp.float32)
    tile_expert, used = _tiles(sizes, bm, m), jnp.int32(rows // bm)
    for n_tasks in (0, 3):
        qw, scale, zero = _expert_stacks(1024, spec, n_tasks, seed=n_tasks)
        tids = jnp.asarray(rng.integers(0, 3, m), jnp.int32) \
            if n_tasks else None
        call = lambda *a, **kw: ops.grouped_quant_matmul(
            x, *a, jnp.asarray(sizes), tile_expert, used, spec, block_m=bm,
            task_ids=tids, impl=impl, **kw)
        for layer in (0, 1, 2):
            got = call(qw, scale, zero, layer=jnp.int32(layer))
            want = call(qw[layer], scale[layer], zero[layer])
            np.testing.assert_array_equal(np.asarray(got)[:rows],
                                          np.asarray(want)[:rows])
