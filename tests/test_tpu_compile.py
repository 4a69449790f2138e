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
compiler updates the donated KV pool in place and that the quantized
kernels read each layer's codes and scales in place from the layer stacks.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports every test file.
"""
import functools
import math
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


@pytest.mark.parametrize("layers", [0, 3], ids=["", "layers"])
@pytest.mark.parametrize("name", list(GMM_CASES))
def test_grouped_expert_kernel_compiles_for_v5e(one_chip, name, layers):
    """Per layer, and layer-stacked with the layer's index (the decode
    scan's call: the expert down projection's K = 1408 is stored
    words-major); a stacked call's operands reach the kernel whole."""
    n, k, rows, bm, n_tasks = GMM_CASES[name]
    e = 64
    spec = QuantSpec(bits=4)
    m = bm * (rows // bm + min(e, rows))
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    lead = (layers,) if layers else ()
    sshape = lead + ((e, n_tasks, n, 1) if n_tasks else (e, n, 1))
    args = [arg((m, k), BF16), arg(lead + (e, n, k // PACK), jnp.uint32),
            arg(sshape, F32), arg(sshape, F32), arg((m // bm,), jnp.int32),
            arg((), jnp.int32), arg((), jnp.int32)]
    if n_tasks:
        args.append(arg((m,), jnp.int32))
    call = lambda *a: qm.quant_gmm_pallas(
        *a[:6], spec=spec, block_m=bm, layer=a[6] if layers else None,
        task_ids=a[7] if n_tasks else None)
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if layers:
        assert _copies(text, [args[1].shape[1:], sshape[1:]]) == ([], [])


# (…, R, C) arrays: codes of the cells' linears and per-channel scales
STORED = [(2048, 176), (4608, 576), (2048, 256), (10944, 256),
          (10944, 352), (1408, 352), (96, 100), (576, 352), (3, 4608, 1),
          (3, 4, 4608, 144)]


@pytest.mark.parametrize("shape", STORED, ids=str)
def test_minor_swapped_matches_the_compilers_layout(one_chip, shape):
    """``quant_matmul.minor_swapped`` says in which order XLA stores a
    32-bit array on the chip; the layer-stacked calls rest on it."""
    import re

    a = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda v: v + 1).lower(a).compile().as_text()
    param = next(l for l in text.splitlines()
                 if "parameter(0)" in l and "ENTRY" not in l)
    order = re.search(r"\]\{([\d,]+)", param).group(1).split(",")
    r = len(shape) - 2
    assert (order[:2] == [str(r), str(r + 1)]) == qm.minor_swapped(shape)


def _dense_cfg():
    """Two bf16 PEQA dense layers: head size 128 and a pool of 2 × 128 MiB,
    as the 7B cells' (32 slots of 2048 rows, 4 KV heads): a pool small
    enough for VMEM is staged there."""
    from repro import configs
    from repro.configs.base import QuantConfig, TuningConfig

    return configs.paper_lm(n_layers=2, d_model=1024, n_heads=8, d_ff=2048,
                            vocab=1024).replace(
        n_kv_heads=4, dtype="bfloat16", tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4))


def _mla_moe_cfg():
    """DeepSeek-V2-Lite's widths in two layers: latent attention, one
    leading dense layer, then one block of 64 routed experts (top 6) and
    two shared ones."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    QuantConfig, TuningConfig)

    return ModelConfig(
        name="mla-moe", family="moe", n_layers=2, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=128, d_ff=10944, vocab_size=1024,
        norm_type="rmsnorm", dtype="bfloat16", n_dense_layers=1,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, n_shared_experts=2,
                      d_ff_expert=1408, expert_sharding="expert"),
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4))


# layer-stacked calls (the decode scan's): (kernel, m, n, k, bits,
# layout, n_tasks, stored planes, layers).  K = 4608 (starcoder2's
# q/k/v/o/up) and the plane-stored K = 14336 are stored words-major, K =
# 18432 row-major (quant_matmul.minor_swapped); a decode of S > 1 tokens
# per slot sends its B·S rows to the GEMM
LAYER_CASES = {
    "gemv_layers_tasks_k4608": ("gemv", 32, 4608, 4608, 4, "nibble", 32, 0,
                                3),
    "gemv_layers_k18432": ("gemv", 32, 4608, 18432, 4, "nibble", 0, 0, 3),
    "gemv_layers_plane_draft_3of4_k14336": ("gemv", 32, 4096, 14336, 3,
                                            "plane", 2, 4, 3),
    "gemm_layers_tasks_k4608": ("matmul", 96, 4608, 4608, 4, "nibble", 32,
                                0, 3),
}


@pytest.mark.parametrize("name", list(LAYER_CASES))
def test_layer_stacked_kernel_compiles_for_v5e(one_chip, name):
    """A layer-indexed call compiles, and its stacked operands reach the
    kernel whole: no copy of them precedes the custom call."""
    kern, m, n, k, bits, layout, n_tasks, planes, layers = LAYER_CASES[name]
    spec = QuantSpec(bits=bits, layout=layout)
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    qshape = ((layers, planes, n, k // PLANE_PACK) if spec.plane
              else (layers, n, k // PACK))
    sshape = (layers, n_tasks, n, 1) if n_tasks else (layers, n, 1)
    args = [arg((m, k), BF16), arg(qshape, jnp.uint32), arg(sshape, F32),
            arg(sshape, F32), arg((), jnp.int32)]
    fn = qm.quant_gemv_pallas if kern == "gemv" else qm.quant_matmul_pallas
    if n_tasks:
        args.append(arg((m,), jnp.int32))
    call = lambda x, w, s, z, l, *t: fn(x, w, s, z, layer=l, spec=spec,
                                        task_ids=t[0] if t else None)
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert _copies(text, [qshape[1:], sshape[1:]]) == ([], [])


def _decode_program(one_chip, caller, cfg=None, impl=None):
    """``caller`` of ``cfg`` (the two-layer dense model by default),
    compiled for the chip with the KV pool donated and three tasks' scales
    resident, its quantized linears on ``impl`` (the default impl when
    None); returns (compiled, pool shapes, params, task stack)."""
    import contextlib

    from repro.core import policies
    from repro.core.scale_bank import task_stack_dim
    from repro.kernels import ops
    from repro.models import registry

    cfg = cfg or _dense_cfg()
    api = registry.build(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(lambda r: policies.transform(api.init(r), cfg, r),
                            jax.random.PRNGKey(0))
    b, cap = 32, 2048
    pool = jax.eval_shape(lambda: api.init_cache(b, cap))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    stack = None
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
    with ops.force_impl(impl) if impl else contextlib.nullcontext():
        lowered = fn.lower(*on_chip(args))
    return lowered.compile(), pool, params, stack


@pytest.mark.parametrize("caller", ["decode_step_slotted", "decode_step"])
def test_decode_updates_kv_pool_in_place(one_chip, caller):
    """The decode layer scan carries the donated pool and writes only the
    new rows: no temporary as large as the pool, the whole pool aliased
    to the output, and no copy that outputs a pool-shaped buffer."""
    import re

    compiled, pool, _, _ = _decode_program(one_chip, caller)
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


def _layer_leaves(tree, keys):
    """One layer's shape of each ``keys`` leaf under ``layers``."""
    out = []
    jax.tree_util.tree_map_with_path(
        lambda kp, l: out.append(l.shape[1:])
        if kp[0].key == "layers" and kp[-1].key in keys else None, tree)
    return out


# an HLO instruction: name, element type, dims, opcode
_INSTR = r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\{[^}]*\} ([\w-]+)\("


def _copies(text: str, shapes) -> tuple:
    """(fusions or copies that output a buffer of one of ``shapes``, in
    any order of its dims and with or without unit dims; word arrays
    bitcast outside a kernel) in a compiled program's text."""
    import re

    dims = lambda shape: tuple(sorted(d for d in shape if d != 1))
    targets = {dims(s) for s in shapes}
    copies, casts = [], []
    fused = False          # inside a fusion's body: no buffers of its own
    for line in text.splitlines():
        if line.rstrip().endswith("{") and not line.startswith(" "):
            fused = "fused" in line.split(" (")[0]
        m = re.match(_INSTR, line)
        if not m or fused:
            continue
        name, dtype, shape, op = m.groups()
        shape = tuple(int(d) for d in shape.split(",") if d)
        if op in ("fusion", "copy", "transpose", "dynamic-slice",
                  "bitcast-convert") and dims(shape) in targets:
            copies.append(line.strip()[:160])
        if "bitcast-convert" in (op, name.split(".")[0]) \
                and dtype in ("s32", "u32") and len(shape) >= 2:
            casts.append(line.strip()[:160])
    return copies, casts


@pytest.mark.parametrize("model", ["dense", "mla_moe"])
@pytest.mark.parametrize("caller", ["decode_step_slotted", "decode_step"])
def test_decode_reads_layer_stacks_in_place(one_chip, model, caller):
    """The quantized kernels take the whole layer-stacked codes and scales
    with the layer's index: no fusion or copy outputs a buffer of one
    layer's codes or of one layer's task-scale stack (in any order of its
    dims, with or without a unit dim), no word array is bitcast outside a
    kernel, and the temporaries stay below one layer's codes."""
    from repro.models.transformer import stacks

    cfg = _dense_cfg() if model == "dense" else _mla_moe_cfg()
    compiled, _, params, stack = _decode_program(one_chip, caller, cfg,
                                                 impl="pallas")
    per_layer = _layer_leaves(params, ("qw",)) + (
        _layer_leaves(stack, ("scale",)) if stack else [])
    copies, casts = _copies(compiled.as_text(), per_layer)
    assert not copies, copies
    assert not casts, casts
    layer_codes = min(
        sum(math.prod(s) * 4 for s in _layer_leaves({"layers": g}, ("qw",)))
        for g in stacks(params["layers"]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_codes, (temp, layer_codes)
