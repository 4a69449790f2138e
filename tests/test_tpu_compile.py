"""The main-path Pallas kernels compile for a TPU v5e at llama3.2-1b widths
(and the grouped expert kernel and MLA's 576-wide W_kva at
DeepSeek-V2-Lite's).

Interpret mode runs these kernels on the CPU, but it does not check what
Mosaic refuses: unaligned blocks, unsupported casts, vector ops it cannot
lower, VMEM limits.  Here each kernel is lowered and compiled for a
described (not attached) v5e chip, and the compiled program must contain
the kernel as a ``tpu_custom_call``.  Widths: d_model 2048, d_ff 8192, so
the (N, K) pairs are the up/gate (8192, 2048) and down (2048, 8192)
projections.  The decode step is compiled whole too, to see that the
compiler updates the donated KV pool in place.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import PACK, PLANE_PACK, QuantSpec
from repro.kernels import quant_matmul as qm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # the TPU compiler otherwise logs under /tmp, shared by every checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


BF16, F32 = jnp.bfloat16, jnp.float32
CASES = {
    # name: (kernel, m, n, k, bits, group, layout, n_tasks, stored planes,
    #        activation dtype[, output dtype])
    "gemm_4bit_channel": ("matmul", 512, 8192, 2048, 4, None, "nibble", 0, 0,
                          BF16),
    # the resident scheduler's prefill: a 64-row prompt, two tasks' scales
    "gemm_tasks_3bit_g128": ("matmul", 64, 2048, 8192, 3, 128, "nibble", 2,
                             0, BF16),
    # chip_smoke's float32 logits check (HIGHEST-precision contraction)
    "gemm_f32_4bit_channel": ("matmul", 80, 2048, 8192, 4, None, "nibble", 0,
                              0, F32),
    "gemv_4bit_channel": ("gemv", 8, 2048, 8192, 4, None, "nibble", 0, 0,
                          BF16),
    "gemv_3bit_g128": ("gemv", 8, 8192, 2048, 3, 128, "nibble", 0, 0, BF16),
    "gemv_tasks_4bit_channel": ("gemv", 8, 8192, 2048, 4, None, "nibble", 4,
                                0, BF16),
    "gemv_tasks_3bit_g128": ("gemv", 8, 2048, 8192, 3, 128, "nibble", 2, 0,
                             BF16),
    "plane_gemv_4bit": ("gemv", 8, 8192, 2048, 4, None, "plane", 0, 4, BF16),
    "plane_gemv_draft_3of4": ("gemv", 8, 2048, 8192, 3, None, "plane", 0, 4,
                              BF16),
    # one shard of a row-parallel `down` on four chips (K 8192 / 4): f32
    # partial sums, added across shards (ops._kernel_matmul)
    "gemv_tasks_row_shard": ("gemv", 8, 2048, 2048, 4, None, "nibble", 2, 0,
                             BF16, F32),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kern, m, n, k, bits, group, layout, n_tasks, planes, xdt, *out = \
        CASES[name]
    spec = QuantSpec(bits=bits, group_size=group, layout=layout)
    g = spec.n_groups(k)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qshape = (planes, n, k // PLANE_PACK) if spec.plane else (n, k // PACK)
    sshape = (n_tasks, n, g) if n_tasks else (n, g)
    args = [arg((m, k), xdt), arg(qshape, jnp.uint32),
            arg(sshape, jnp.float32), arg(sshape, jnp.float32)]
    fn = functools.partial(
        qm.quant_gemv_pallas if kern == "gemv" else qm.quant_matmul_pallas,
        out_dtype=out[0] if out else None)
    if n_tasks:
        args.append(arg((m,), jnp.int32))
        call = lambda x, w, s, z, t: fn(x, w, s, z, task_ids=t, spec=spec)
    else:
        call = functools.partial(fn, spec=spec)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# DeepSeek-V2-Lite's MLA down-projection: 576 outputs (a 512-wide latent
# and a 64-wide rope key), not a multiple of block_n = 128
WKVA_CASES = {"gemv_wkva_576": ("gemv", 32, 0), "gemm_wkva_576": ("matmul",
                                                                  512, 0),
              "gemv_tasks_wkva_576": ("gemv", 32, 32)}


@pytest.mark.parametrize("name", list(WKVA_CASES))
def test_wkva_576_compiles_for_v5e(one_chip, name):
    kern, m, n_tasks = WKVA_CASES[name]
    spec = QuantSpec(bits=4)
    n, k = 576, 2048
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    sshape = (n_tasks, n, 1) if n_tasks else (n, 1)
    args = [arg((m, k), BF16), arg((n, k // PACK), jnp.uint32),
            arg(sshape, F32), arg(sshape, F32)]
    fn = qm.quant_gemv_pallas if kern == "gemv" else qm.quant_matmul_pallas
    if n_tasks:
        args.append(arg((m,), jnp.int32))
        call = lambda x, w, s, z, t: fn(x, w, s, z, task_ids=t, spec=spec)
    else:
        call = functools.partial(fn, spec=spec)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the grouped expert kernel at DeepSeek-V2-Lite's widths (d 2048, expert
# 1408, 64 experts): gate/up at K 2048 and down at K 1408; decode's 32
# slots × top-6 rows in 16-row tiles with 32 tasks' scales, and a 512-token
# prefill's rows in 64-row tiles under one task's
GMM_CASES = {
    "gmm_decode_up_k2048": (1408, 2048, 32 * 6, 16, 32),
    "gmm_decode_down_k1408": (2048, 1408, 32 * 6, 16, 32),
    "gmm_prefill_up_k2048": (1408, 2048, 512 * 6, 64, 0),
    "gmm_prefill_down_k1408": (2048, 1408, 512 * 6, 64, 0),
}


@pytest.mark.parametrize("name", list(GMM_CASES))
def test_grouped_expert_kernel_compiles_for_v5e(one_chip, name):
    n, k, rows, bm, n_tasks = GMM_CASES[name]
    e = 64
    spec = QuantSpec(bits=4)
    m = bm * (rows // bm + min(e, rows))
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    sshape = (e, n_tasks, n, 1) if n_tasks else (e, n, 1)
    args = [arg((m, k), BF16), arg((e, n, k // PACK), jnp.uint32),
            arg(sshape, F32), arg(sshape, F32), arg((m // bm,), jnp.int32),
            arg((), jnp.int32)]
    if n_tasks:
        args.append(arg((m,), jnp.int32))
    call = lambda *a: qm.quant_gmm_pallas(
        *a[:6], spec=spec, block_m=bm,
        task_ids=a[6] if n_tasks else None)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_program(one_chip, caller):
    """``caller`` of a two-layer bf16 PEQA dense model, compiled for the
    chip with the KV pool donated; returns (compiled, pool shapes).  Head
    size 128 and a pool of 2 × 128 MiB, as the 7B cells' (32 slots of 2048
    rows, 4 KV heads): a pool small enough for VMEM is staged there."""
    from repro import configs
    from repro.configs.base import QuantConfig, TuningConfig
    from repro.core import policies
    from repro.core.scale_bank import task_stack_dim
    from repro.models import registry

    cfg = configs.paper_lm(n_layers=2, d_model=1024, n_heads=8, d_ff=2048,
                           vocab=1024).replace(
        n_kv_heads=4, dtype="bfloat16", tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4))
    api = registry.build(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(lambda r: policies.transform(api.init(r), cfg, r),
                            jax.random.PRNGKey(0))
    b, cap = 32, 2048
    pool = jax.eval_shape(lambda: api.init_cache(b, cap))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if caller == "decode_step":
        args = (params, pool, i32(b, 1), i32())
        donate = 1
    else:
        def stacked(kp, leaf):
            if kp[-1].key not in ("scale", "zero"):
                return None
            d = task_stack_dim(leaf.ndim)
            return jax.ShapeDtypeStruct(
                leaf.shape[:d] + (3,) + leaf.shape[d:], leaf.dtype)
        stack = jax.tree_util.tree_map_with_path(stacked, params)
        args = (params, stack, pool, i32(b, 1), i32(b), i32(b))
        donate = 2
    fn = jax.jit(getattr(api, caller), donate_argnums=(donate,))
    return fn.lower(*on_chip(args)).compile(), pool


@pytest.mark.parametrize("caller", ["decode_step_slotted", "decode_step"])
def test_decode_updates_kv_pool_in_place(one_chip, caller):
    """The decode layer scan carries the donated pool and writes only the
    new rows: no temporary as large as the pool, the whole pool aliased
    to the output, and no copy that outputs a pool-shaped buffer."""
    import re

    compiled, pool = _decode_program(one_chip, caller)
    pool_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(pool))
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < pool_bytes, (ma.temp_size_in_bytes,
                                                pool_bytes)
    assert ma.alias_size_in_bytes == pool_bytes
    shape = ",".join(map(str, pool["k"].shape))
    copies = re.findall(rf"= bf16\[{shape}\]\{{[^}}]*\}} copy\(",
                        compiled.as_text())
    assert not copies, copies
