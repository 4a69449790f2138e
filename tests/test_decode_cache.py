"""The decode step writes only its new K/V rows into the stacked KV pool.

``transformer.decode_tokens`` carries the whole (L, B, C, Hkv, D) pool
through its layer scan; each layer writes its new rows in place and reads
its own layer back.  Every decode caller shares that path, so each case
below runs one decode step through a different caller and checks:

  * the returned pool equals the input bit for bit everywhere except rows
    (l, b, pos[b] .. pos[b]+S-1) (modulo the capacity on an SWA ring);
  * those rows hold the layer's new K/V (and int8 scales);
  * the logits equal a per-layer Python loop over the sliced layers that
    writes each layer's rows with ``.at[].set``.

On a (2, 4) mesh the batch-sharded pool's row write stays shard-local: no
collective in the compiled decode steps moves the pool, the new rows or
their indices.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import subproc_env

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.core import scale_bank as sb
from repro.kernels import ops
from repro.models import attention, common, linear, registry, transformer

B, C = 4, 16
TOL = dict(rtol=2e-4, atol=2e-4)

# name: (config changes, pos, tokens per slot, caller)
CASES = {
    "scalar_pos": ({}, 6, 1, "step"),
    "slotted_pos": ({}, [0, 5, 9, 15], 1, "step"),
    "task_stack": ({}, [3, 0, 12, 7], 1, "slotted"),
    "verify_s3": ({}, [0, 5, 9, 13], 3, "tokens"),
    "int8_kv": ({"kv_cache_dtype": "int8"}, [2, 7, 11, 4], 1, "step"),
    # capacity 6: every row has passed the ring's end at least once
    "swa_ring_wraps": ({"swa_window": 6}, [6, 13, 23, 8], 1, "step"),
}


def _setup(changes):
    cfg = configs.paper_lm(n_layers=3, d_model=64, n_heads=4, d_ff=96,
                           vocab=128).replace(
        n_kv_heads=2, tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, n_grid=2), **changes)
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    params, _ = policies.prepare(api.init(rng), cfg, rng)
    return cfg, api, params


def _random_pool(api, seed):
    """A pool of the decode step's shape and dtypes filled with noise, so a
    write to any row that should not move shows."""
    pool = api.init_cache(B, C)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(pool))
    out = {}
    for key, (name, leaf) in zip(keys, sorted(pool.items())):
        if leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(key, leaf.shape, -127, 128,
                                           jnp.int32).astype(jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(key, leaf.shape, jnp.float32,
                                           0.005, 0.02).astype(leaf.dtype)
        else:
            out[name] = jax.random.normal(key, leaf.shape, leaf.dtype)
    return out


def _task_stack(params):
    """Three tasks' scales stacked as the resident stack holds them."""
    base = sb.extract_scales(params, include_zero=True)
    rng = np.random.default_rng(5)
    tasks = [{k: (v * rng.uniform(0.8, 1.2, v.shape)).astype(v.dtype)
              for k, v in base.items()} for _ in range(3)]
    return jax.tree.map(jnp.asarray, sb.stack_scales(base, tasks))


def _reference(params, pool, tokens, pos, cfg, stack=None, task_ids=None):
    """Per-layer Python loop: slice each layer, write its rows into that
    layer's cache with ``.at[].set``, attend over it.  Returns (logits
    (B, S, V), {name: (L, B, S, ...) rows written}, rows (B, S))."""
    b, s = tokens.shape
    if stack is not None and s > 1:
        task_ids = jnp.repeat(task_ids, s)
    rows = np.broadcast_to(np.reshape(pos, (-1, 1)) + np.arange(s), (b, s))
    if cfg.swa_window is not None:
        rows = rows % pool["k"].shape[2]
    bi = np.arange(b)[:, None]
    layer_of = lambda tree, l: jax.tree.map(lambda x: x[l], tree)
    pool = dict(pool)
    written = {name: [] for name in pool}
    h = common.embed_apply(params["embed"], tokens, cfg)
    for l in range(cfg.n_layers):
        lp = layer_of(params["layers"], l)
        slots = None if stack is None else (task_ids,
                                            layer_of(stack["layers"], l))
        hin = common.norm_apply(lp["ln1"], h, cfg)
        q, k, v = attention._qkv(lp["attn"], hin, cfg,
                                 slots=linear.slot_entry(slots, "attn"))
        if cfg.use_rope:
            q, k = attention._rope_decode(q, k, pos, cfg)
        if "k_scale" in pool:
            k8, ks = attention.quantize_kv(k)
            v8, vs = attention.quantize_kv(v)
            new = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
        else:
            new = {"k": k, "v": v}
        layer = {}
        for name, val in new.items():
            layer[name] = pool[name][l].at[bi, rows].set(
                val.astype(pool[name].dtype))
            pool[name] = pool[name].at[l].set(layer[name])
            written[name].append(layer[name][bi, rows])
        if "k_scale" in pool:
            ck = attention.dequantize_kv(layer["k"], layer["k_scale"], h.dtype)
            cv = attention.dequantize_kv(layer["v"], layer["v_scale"], h.dtype)
        else:
            ck, cv = layer["k"], layer["v"]
        o = ops.attention(q, ck, cv, causal=True, offset=pos,
                          impl=cfg.attn_impl).reshape(b, s, -1)
        h = h + attention._out(lp["attn"], o, cfg,
                               linear.slot_entry(slots, "attn"))
        h = h + common.mlp_apply(lp["mlp"],
                                 common.norm_apply(lp["ln2"], h, cfg), cfg,
                                 slots=linear.slot_entry(slots, "mlp"))
    h = common.norm_apply(params["final_norm"], h, cfg)
    head = None if stack is None else linear.slot_entry((task_ids, stack),
                                                        "lm_head")
    logits = common.head_apply(params, params["embed"], h, cfg, slots=head)
    return logits, {n: jnp.stack(w) for n, w in written.items()}, rows


@pytest.mark.parametrize("case", list(CASES))
def test_decode_writes_only_new_rows(case):
    changes, pos, s, caller = CASES[case]
    cfg, api, params = _setup(changes)
    pool = _random_pool(api, seed=11)
    pos = jnp.asarray(pos, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, s), 0,
                                cfg.vocab_size)
    stack = task_ids = None
    if caller == "step":
        logits, out = jax.jit(api.decode_step)(params, pool, tokens, pos)
        logits = logits[:, None]
    elif caller == "tokens":
        # the shared decode body at S = 3 tokens per slot
        logits, out = jax.jit(lambda p, c, t, q: transformer.decode_tokens(
            p, c, t, q, cfg)[:2])(params, pool, tokens, pos)
    else:
        stack = _task_stack(params)
        task_ids = jnp.asarray([2, 0, 1, 2], jnp.int32)
        logits, out = jax.jit(api.decode_step_slotted)(
            params, stack, pool, tokens, pos, task_ids)
        logits = logits[:, None]
    ref_logits, written, rows = _reference(
        params, pool, tokens, pos, cfg, stack, task_ids)

    assert jax.tree.structure(out) == jax.tree.structure(pool)
    keep = np.ones((cfg.n_layers, B, C if cfg.swa_window is None
                    else cfg.swa_window), bool)
    keep[:, np.arange(B)[:, None], rows] = False
    for name in pool:
        got, before = np.asarray(out[name]), np.asarray(pool[name])
        assert got.dtype == before.dtype and got.shape == before.shape
        np.testing.assert_array_equal(got[keep], before[keep],
                                      err_msg=f"{case}: {name} moved")
        new_rows = got[:, np.arange(B)[:, None], rows]
        want = np.asarray(written[name])
        if got.dtype == np.int8:
            np.testing.assert_array_equal(new_rows, want, err_msg=name)
        else:
            np.testing.assert_allclose(new_rows.astype(np.float32),
                                       want.astype(np.float32), **TOL,
                                       err_msg=f"{case}: {name} rows")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               **TOL)


_MESH_TEST = textwrap.dedent("""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro import configs
    from repro.configs.base import QuantConfig, TuningConfig
    from repro.core import policies
    from repro.core import scale_bank as sb
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.launch.mesh import make_mesh
    from repro.models import registry
    from repro.train.serve import Engine

    ctx = dctx.make_ctx(make_mesh((2, 4), ("data", "model")))
    cfg = configs.paper_lm(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                           vocab=512).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)
    bank = sb.ScaleBank()
    bank.add("A", p)
    eng = Engine(api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
                 bank=bank, ctx=ctx, logitshard=True)
    for hlo in (eng.continuous_decode_hlo(4, 40),
                eng.slotted_decode_hlo(4, 40)):
        assert "scatter" in hlo
        coll = re.findall(r"= (\\w+)\\[[0-9,]*\\]\\S* (all-gather|all-reduce|"
                          r"collective-permute|all-to-all|reduce-scatter)", hlo)
        # the row-parallel partial sums and the packed codes' gather; the
        # K/V rows, their row indices and the pool never cross shards
        assert coll and set(coll) <= {("f32", "all-reduce"),
                                      ("u32", "all-gather")}, set(coll)
    print("MESH_OK")
""")


def test_decode_pool_write_stays_shard_local():
    res = subprocess.run([sys.executable, "-c", _MESH_TEST],
                         capture_output=True, text=True, timeout=600,
                         env=subproc_env())
    assert "MESH_OK" in res.stdout, res.stderr[-3000:]
