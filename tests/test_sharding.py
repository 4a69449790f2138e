"""Sharding rules + distributed lowering tests.

The compile tests run in a SUBPROCESS (jax pins the device count at first
init; the main test process must keep seeing 1 CPU device)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import TuningConfig
from repro.core import policies
from repro.dist import sharding as sh
from repro.models import registry

from conftest import subproc_env


def test_spec_rules_dense():
    assert sh.spec_for_path("layers/attn/wq/w", 3) == P(None, "model")
    assert sh.spec_for_path("layers/attn/wq/w", 2) == P("model")
    assert sh.spec_for_path("layers/attn/wo/w", 3) == P(None, None, "model")
    assert sh.spec_for_path("layers/mlp/down/qw", 3) == P(None, None, "model")
    assert sh.spec_for_path("layers/mlp/up/scale", 3) == P(None, "model")
    assert sh.spec_for_path("layers/mlp/down/scale", 3) == P()
    assert sh.spec_for_path("embed/emb", 2) == P("model")
    assert sh.spec_for_path("layers/ln1/g", 2) == P()
    assert sh.spec_for_path("layers/attn/wq/b", 2) == P(None, "model")


def test_spec_rules_moe_and_ssm():
    assert sh.spec_for_path("layers/moe/experts_ep/up/w", 4) == \
        P(None, "model")
    assert sh.spec_for_path("layers/moe/experts/up/w", 4) == \
        P(None, None, "model")
    assert sh.spec_for_path("layers/moe/experts/down/w", 4) == \
        P(None, None, None, "model")
    assert sh.spec_for_path("layers/moe/router/w", 2) == P()
    assert sh.spec_for_path("mamba_groups/xproj/w", 4) == P(None, None, "model")
    assert sh.spec_for_path("mamba_groups/conv/w", 4) == P(None, None, "model")
    assert sh.spec_for_path("mamba_groups/A_log", 3) == P(None, None, "model")
    assert sh.spec_for_path("layers/attn/wq/lora_a", 3) == P()
    assert sh.spec_for_path("layers/attn/wq/lora_b", 3) == P(None, "model")


def test_spec_rules_leading_dense_down():
    """A MoE stack's leading dense ``down`` is stored by output rows (its
    packed d_ff need not split the model axis); every other ``down`` keeps
    the row-parallel layout."""
    assert sh.spec_for_path("layers/dense/mlp/down/qw", 3) == \
        P(None, "model")
    assert sh.spec_for_path("layers/dense/mlp/down/scale", 3) == \
        P(None, "model")
    assert sh.spec_for_path("layers/dense/mlp/up/qw", 3) == P(None, "model")
    assert sh.spec_for_path("layers/mlp/down/qw", 3) == P(None, None, "model")
    assert sh.spec_for_path("layers/moe/shared/down/qw", 3) == \
        P(None, None, "model")


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_full_configs_divide_production_mesh(arch):
    """Every param of every FULL config must divide the 16-way model axis.
    Checked on abstract shapes (no allocation)."""
    cfg = configs.get_config(arch)
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    aparams = jax.eval_shape(
        lambda: policies.transform(api.init(rng), cfg, rng))
    sizes = {"data": 16, "model": 16}

    def check(kp, leaf):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = sh.spec_for_path(path, len(leaf.shape))
        for dim, ax in enumerate(tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= sizes[a]
            assert leaf.shape[dim] % total == 0, \
                f"{arch}: {path} dim{dim}={leaf.shape[dim]} % {total}"

    jax.tree_util.tree_map_with_path(check, aparams)


_SUBPROC_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro import configs
    from repro.configs.base import ShapeConfig, TuningConfig, MoEConfig, TrainConfig
    from repro.core import policies
    from repro.dist import context as dctx
    from repro.models import registry
    from repro.optim.adamw import make_optimizer
    from repro.train import step as step_mod

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = dctx.make_ctx(mesh)
    rng = jax.random.PRNGKey(0)
    tcfg = TrainConfig()
    cfg = configs.paper_lm(n_layers=2, d_model=128, n_heads=8, d_ff=256,
                           vocab=512).replace(
        tuning=TuningConfig(mode="peqa"))
    shape = ShapeConfig("t", 64, 4, "train")
    api = registry.build(cfg)
    ap = jax.eval_shape(lambda: policies.transform(api.init(rng), cfg, rng))
    mask = policies.make_mask(ap, cfg)
    opt = make_optimizer(tcfg.optim, tcfg.steps)
    astate = {"params": ap,
              "opt": jax.eval_shape(lambda p: opt.init(p, mask), ap),
              "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = api.input_specs(shape)
    with dctx.use_mesh(ctx):
        ts = step_mod.build_train_step(api, cfg, tcfg, mask, opt, mesh=mesh,
                                       state_example=astate,
                                       batch_example=batch)
        compiled = ts.lower(astate, batch).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0]
    assert cost.get("flops", 0) > 0
    # MoE expert-parallel decode also compiles
    cfgm = cfg.replace(name="m", family="moe", d_ff=64,
                       moe=MoEConfig(n_experts=8, top_k=2,
                                     expert_sharding="expert"))
    from repro.launch import dryrun as dr
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import sharding as shard_rules
    apim = registry.build(cfgm)
    apm = jax.eval_shape(lambda: policies.transform(apim.init(rng), cfgm, rng))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shard_rules.param_specs(apm),
                          is_leaf=lambda x: isinstance(x, P))
    acache = jax.eval_shape(lambda: apim.init_cache(4, 64))
    cspec = dr._cache_specs_tree(ctx, acache, 4, True)
    to_ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    with dctx.use_mesh(ctx):
        f = jax.jit(apim.decode_step,
                    in_shardings=(pshard, to_ns(cspec),
                                  NamedSharding(mesh, P("data", None)),
                                  NamedSharding(mesh, P())))
        f.lower(apm, acache, tok, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    print("SUBPROC_OK")
""")


def test_sharded_compile_subprocess():
    """Train-step + MoE decode lower&compile on a (2,4) host-device mesh."""
    res = subprocess.run([sys.executable, "-c", _SUBPROC_TEST],
                         capture_output=True, text=True, timeout=900,
                         env=subproc_env())
    assert "SUBPROC_OK" in res.stdout, res.stderr[-3000:]


_MOE_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.configs.base import MoEConfig, QuantConfig, TuningConfig
    from repro.core import policies
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.launch.mesh import make_mesh
    from repro.models import moe

    d_ff, sharding, shared, group, layout = sys.argv[1:]
    cfg = configs.paper_lm(n_layers=1, d_model=64, n_heads=4,
                           d_ff=int(d_ff), vocab=128).replace(
        family="moe", tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, group_size=None if group == "none"
                          else int(group), layout=layout),
        moe=MoEConfig(n_experts=4, top_k=2, n_shared_experts=int(shared),
                      expert_sharding=sharding))
    rng = jax.random.PRNGKey(0)
    p = policies.transform({"moe": moe.init(rng, cfg)}, cfg, rng)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64), jnp.float32)

    def loss(p):
        y, aux, _ = moe.apply(p, x, cfg)
        return jnp.sum(y * y) + aux, y

    def scale_grads(p):
        g = jax.grad(lambda p: loss(p)[0], allow_int=True)(p)
        return {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_leaves_with_path(g)
                if "scale" in jax.tree_util.keystr(k)}

    run = lambda p: (loss(p)[1], scale_grads(p))
    y1, g1 = jax.jit(run)(p)
    ctx = dctx.make_ctx(make_mesh((1, 4), ("data", "model")))
    pm = jax.device_put(p, shard_rules.named_shardings(ctx, p))
    with dctx.use_mesh(ctx):   # a new function: jit caches by identity
        y4, g4 = jax.jit(lambda p: run(p))(pm)

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return np.max(np.abs(a - b)) <= 1e-5 * np.max(np.abs(b))
    assert close(y4, y1), float(np.max(np.abs(np.asarray(y4 - y1))))
    assert g1 and all(close(g4[k], g1[k]) for k in g1), "scale grads differ"
    print("SUBPROC_OK")
""")


@pytest.mark.parametrize("d_ff,sharding,shared,group,layout", [
    # deepseek-like: experts sharded whole, a shared expert whose down codes
    # (8 words, one tile) do not split on tiles over 4 shards
    ("64", "expert", "1", "none", "nibble"),
    # mixtral-like d_ff-sharded experts: 512-word rows, one tile per shard
    ("4096", "tensor", "0", "none", "nibble"),
    # d_ff-sharded experts cut inside each shard, 2 quant groups per shard
    ("256", "tensor", "1", "32", "nibble"),
    ("256", "tensor", "1", "none", "plane"),
])
def test_moe_mesh_matches_one_device(d_ff, sharding, shared, group, layout):
    """MoE output and scale gradients on a (1, 4) mesh equal the
    single-device block, whether or not the row-parallel codes split on
    packing tiles."""
    res = subprocess.run(
        [sys.executable, "-c", _MOE_TEST, d_ff, sharding, shared, group,
         layout], capture_output=True, text=True, timeout=600,
        env=subproc_env())
    assert "SUBPROC_OK" in res.stdout, res.stderr[-3000:]


_KSPLIT_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.quant import QTensor, QuantSpec
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    k, group, n_tasks, layout = (int(sys.argv[1]), sys.argv[2],
                                 int(sys.argv[3]), sys.argv[4])
    spec = QuantSpec(bits=4, group_size=None if group == "none"
                     else int(group), layout=layout)
    n, m = 256, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    qt = QTensor.quantize(jax.random.normal(keys[0], (n, k)), spec)
    x = jax.random.normal(keys[1], (m, k), jnp.float32)
    stack = lambda a: jnp.stack([a * (1 + 0.1 * t) for t in range(n_tasks)])
    tids = jnp.arange(m, dtype=jnp.int32) % max(n_tasks, 1)

    def fwd(x, qw, s, z):
        if n_tasks:
            return ops.quant_matmul_slotted(
                x, qw, stack(s), stack(z), tids, spec, impl="interpret",
                row_parallel=True)
        return ops.quant_matmul(x, qw, s, z, spec, impl="interpret",
                                row_parallel=True)

    args = (x, qt.qw, qt.scale, qt.zero)
    want = jax.jit(fwd)(*args)
    ctx = dctx.make_ctx(make_mesh((1, 4), ("data", "model")))
    tree = {"down": {"qw": qt.qw, "scale": qt.scale, "zero": qt.zero}}
    placed = jax.device_put(tree, shard_rules.named_shardings(ctx, tree))
    with dctx.use_mesh(ctx):   # a new function: jit caches by identity
        f = jax.jit(lambda *a: fwd(*a))
        got = f(x, *(placed["down"][key] for key in ("qw", "scale", "zero")))
        hlo = f.lower(*args).compile().as_text()
    err = float(np.max(np.abs(np.asarray(got - want))))
    assert err <= 1e-5 * float(np.max(np.abs(np.asarray(want)))), err
    print("SUBPROC_OK", "all-reduce" in hlo)
""")


@pytest.mark.parametrize("k,group,n_tasks,layout,split", [
    (4096, "none", 0, "nibble", True),    # 512 words: one tile per shard
    (4096, "128", 2, "nibble", True),     # + groups and task-stacked scales
    (16384, "none", 0, "plane", True),
    (512, "none", 0, "nibble", False),    # one 64-word tile: split by N
])
def test_row_parallel_kernel_on_mesh(k, group, n_tasks, layout, split):
    """A row-parallel quantized linear on a (1, 4) mesh equals the
    one-device kernel; where its word shards hold whole tiles the kernel
    contracts them in place and adds the partial sums (an all-reduce)."""
    res = subprocess.run(
        [sys.executable, "-c", _KSPLIT_TEST, str(k), group, str(n_tasks),
         layout], capture_output=True, text=True, timeout=600,
        env=subproc_env())
    assert f"SUBPROC_OK {split}" in res.stdout, res.stderr[-3000:]


_PP_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.dist.pipeline_par import pipeline_apply
    mesh = make_mesh((4,), ("stage",))
    L, B, D = 8, 8, 16
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) / np.sqrt(D))
    x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
    layer_fn = lambda w, h: jnp.tanh(h @ w)
    ref = x
    for i in range(L):
        ref = layer_fn(ws[i], ref)
    out = jax.jit(lambda w, x: pipeline_apply(layer_fn, w, x, mesh))(ws, x)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    g1 = jax.grad(lambda w: jnp.sum(pipeline_apply(layer_fn, w, x, mesh)))(ws)
    def seq(w):
        h = x
        def body(h, wi):
            return layer_fn(wi, h), None
        h, _ = jax.lax.scan(body, h, w)
        return jnp.sum(h)
    g2 = jax.grad(seq)(ws)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-5
    print("SUBPROC_OK")
""")


def test_pipeline_parallel_subprocess():
    """GPipe over shard_map+ppermute matches the sequential scan (fwd+bwd)."""
    res = subprocess.run([sys.executable, "-c", _PP_TEST],
                         capture_output=True, text=True, timeout=600,
                         env=subproc_env())
    assert "SUBPROC_OK" in res.stdout, res.stderr[-3000:]
