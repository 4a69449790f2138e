"""Mesh-native serving: sharded ScaleBank swaps + shard-local sampling.

Subprocess tests (jax pins the device count at first init; the main test
process must keep seeing 1 CPU device).  One child process covers the whole
serving acceptance surface on a (2, 4) mesh:

  * post-swap scale leaves land exactly on their ``param_specs`` shardings,
  * the swap HLO contains NO collective (the layout is swap-aligned, so a
    task switch moves per-shard local bytes only),
  * the ``logitshard`` shard-local argmax matches the gathered argmax
    BIT-EXACTLY (including cross-shard and within-shard value ties),
  * the logitshard decode step contains no vocab-dimension all-gather
    while the replicated baseline contains exactly the one it deletes,
  * end-to-end: mesh-engine greedy generation equals the host engine's.

Further children cover Gumbel-max and nucleus (top-p) sampling — both
bit-identical across mesh shapes and off-mesh — and continuous batching on
the mesh.
"""
import subprocess
import sys
import textwrap

from conftest import subproc_env

_SERVE_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import configs
    from repro.configs.base import QuantConfig, TuningConfig
    from repro.core import policies
    from repro.core import scale_bank as sb
    from repro.core.treepath import path_str
    from repro.dist import context as dctx, sampling
    from repro.dist import sharding as shard_rules
    from repro.launch import hlo_stats
    from repro.models import registry
    from repro.train.serve import Engine

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = dctx.make_ctx(mesh)
    cfg = configs.paper_lm(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                           vocab=512).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)     # host master copy (swaps donate)

    bank = sb.ScaleBank()
    bank.add("A", p)
    rngs = np.random.default_rng(7)
    bank.tasks["B"] = {k: (v * rngs.uniform(0.5, 1.5, v.shape)
                           ).astype(v.dtype)
                       for k, v in bank.tasks["A"].items()}

    # ---- sharded swap: shardings == param_specs, no collectives --------
    assert shard_rules.validate_for_mesh(p, mesh) == []
    sp = jax.device_put(p, shard_rules.named_shardings(ctx, p))
    swapped = bank.switch(sp, "B", ctx=ctx)

    def chk(kp, leaf):
        path = path_str(kp)
        if path.split("/")[-1] == "scale":
            want = jax.sharding.NamedSharding(
                mesh, shard_rules.spec_for_path(path, leaf.ndim))
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim), \\
                (path, leaf.sharding, want)
    jax.tree_util.tree_map_with_path(chk, swapped)

    hlo = sb.swap_hlo(sp, bank.tasks["B"], ctx)
    coll = hlo_stats.collective_stats(hlo)
    assert coll["total_bytes"] == 0.0, coll
    for kind in ("all-gather", "all-reduce", "collective-permute"):
        assert kind + "(" not in hlo, kind

    # swapped values match the host path bit-exactly
    ref = bank.switch(jax.tree.map(jnp.asarray, p), "B")
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(swapped)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-device payload is the sharded fraction of the scale set
    assert bank.local_nbytes("B", ctx) < bank.nbytes("B")

    # ---- cache batch-dim inference survives extent collisions ----------
    # batch == n_layers == 2: the attn cache is (L=2, B=2, C, H, D) — the
    # structural inference must shard dim 1 (batch), never dim 0 (layers)
    bdims = shard_rules.cache_batch_dims(api.init_cache, 2, 16)
    acache = jax.eval_shape(lambda: api.init_cache(2, 16))
    cspecs = shard_rules.cache_specs(ctx, acache, 2, True,
                                     n_kv_heads=cfg.n_kv_heads,
                                     batch_dims=bdims)
    # a PartitionSpec stores a one-axis tuple as the bare axis name
    dax = jax.sharding.PartitionSpec(ctx.data_axes)[0]
    for leaf, bd, cs in zip(jax.tree.leaves(acache), jax.tree.leaves(bdims),
                            jax.tree.leaves(cspecs)):
        assert bd != 0, (leaf.shape, bd)       # dim 0 is the layer stack
        if bd >= 0:
            assert tuple(cs)[bd] == dax, (leaf.shape, bd, cs)
            assert all(ax != dax for i, ax in enumerate(tuple(cs))
                       if i != bd), (leaf.shape, cs)

    # ---- shard-local argmax: bit-exact vs gathered argmax --------------
    B, V = 4, cfg.vocab_size
    lg = rngs.normal(size=(B, V)).astype(np.float32)
    lg[0, 7] = lg[0, 300] = 99.0      # tie ACROSS shards -> first wins
    lg[2, 130] = lg[2, 131] = 55.0    # tie WITHIN a shard
    glg = jax.device_put(jnp.asarray(lg), ctx.logits_sharding(B))
    got = np.asarray(jax.jit(sampling.shard_argmax(ctx, B))(glg))
    np.testing.assert_array_equal(got, np.argmax(lg, axis=-1))
    v, i = jax.jit(sampling.shard_topk(ctx, B, 5))(glg)
    vr, ir = jax.lax.top_k(jnp.asarray(lg), 5)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))

    # ---- decode HLO: logitshard deletes the vocab all-gather -----------
    mk = lambda ls: Engine(
        api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
        bank=bank, ctx=ctx, logitshard=ls)
    eng_base, eng_ls = mk(False), mk(True)
    ag_base = hlo_stats.allgather_extent_count(eng_base.decode_hlo(B, 32), V)
    ag_ls = hlo_stats.allgather_extent_count(eng_ls.decode_hlo(B, 32), V)
    assert ag_ls == 0, f"logitshard decode still all-gathers vocab: {ag_ls}"
    assert ag_base >= 1, "replicated baseline should gather the logits"

    # ---- end-to-end: mesh generation == host generation ----------------
    prompt = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (B, 1))
    host = Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)
    o_host = np.asarray(host.generate(prompt, n_new=6))
    o_mesh = np.asarray(eng_ls.generate(
        jax.device_put(prompt, ctx.sharding()), n_new=6))
    np.testing.assert_array_equal(o_host, o_mesh)

    # swap on the mesh engine steers generation, and blocks on all leaves
    dt = eng_ls.switch_task("B")
    assert dt > 0
    host.switch_task("B")
    o_host_b = np.asarray(host.generate(prompt, n_new=6))
    o_mesh_b = np.asarray(eng_ls.generate(
        jax.device_put(prompt, ctx.sharding()), n_new=6))
    np.testing.assert_array_equal(o_host_b, o_mesh_b)
    assert not np.array_equal(o_mesh, o_mesh_b), \\
        "task B scales must change the continuation"
    print("SUBPROC_OK")
""")


def test_sharded_serving_subprocess():
    """Sharded swaps + shard-local sampling on a (2,4) host-device mesh."""
    res = subprocess.run([sys.executable, "-c", _SERVE_TEST],
                         capture_output=True, text=True, timeout=900,
                         env=subproc_env())
    assert "SUBPROC_OK" in res.stdout, res.stderr[-3000:]


_CONT_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import configs
    from repro.configs.base import QuantConfig, TuningConfig
    from repro.core import policies
    from repro.core import scale_bank as sb
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.launch import hlo_stats
    from repro.launch.serve import place_prompt
    from repro.models import registry
    from repro.serve import ServeConfig
    from repro.train.serve import Engine, Request

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = dctx.make_ctx(mesh)
    cfg = configs.paper_lm(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                           vocab=512).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)

    bank = sb.ScaleBank()
    bank.add("A", p)
    rngs = np.random.default_rng(7)
    bank.tasks["B"] = {k: (v * rngs.uniform(0.5, 1.5, v.shape)
                           ).astype(v.dtype)
                       for k, v in bank.tasks["A"].items()}

    host = Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)
    emesh = Engine(api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
                   bank=bank, ctx=ctx, logitshard=True)

    # ---- launcher prompt placement: batch-sharded, not replicated ------
    prompt = place_prompt(jnp.zeros((4, 8), jnp.int32), ctx)
    want = ctx.sharding(ctx.data_axes, None)
    assert prompt.sharding.is_equivalent_to(want, 2), prompt.sharding

    # ---- continuous mesh serving == host serving == lockstep -----------
    reqs = [Request(tokens=(np.arange(6, dtype=np.int32) * (i + 1)) % 512,
                    n_new=[4, 7, 3, 9][i % 4],
                    task=["A", "B"][(i // 4) % 2], arrival_step=i // 2)
            for i in range(8)]
    host.switch_task("A"); emesh.switch_task("A")
    rep_h = host.serve(reqs, ServeConfig(n_slots=4))
    host.switch_task("A"); emesh.switch_task("A")
    rep_m = emesh.serve(reqs, ServeConfig(n_slots=4))
    assert rep_m.bubble_slot_steps == 0
    # auto -> resident: prefill reads the stack row, so admission is
    # swap-free (zero switches); the drain path still swaps per task run
    assert rep_m.scheduler == rep_h.scheduler == "resident"
    assert rep_m.switches == rep_h.switches == 0
    rep_d = emesh.serve(reqs, ServeConfig(n_slots=4, scheduler="drain"))
    assert rep_d.switches >= 1
    assert rep_d.tokens == rep_m.tokens
    for i in range(len(reqs)):
        assert rep_h.tokens[i] == rep_m.tokens[i], i
    for i, r in enumerate(reqs):                       # lockstep oracle
        host.switch_task(r.task)
        ref = np.asarray(host.generate(
            jnp.asarray(r.tokens)[None], n_new=r.n_new))[0, 6:]
        assert np.array_equal(ref, np.asarray(rep_h.tokens[i])), i

    # ---- post-admit slot-pool shardings == cache_specs -----------------
    emesh.switch_task("A")
    pool = emesh.open_pool(4, 24)
    emesh.admit(pool, Request(tokens=np.arange(6, dtype=np.int32), n_new=4,
                              task="A"))
    want_sh = emesh._cache_shardings(pool.cache, 4)
    for leaf, w in zip(jax.tree.leaves(pool.cache),
                       jax.tree.leaves(want_sh)):
        assert leaf.sharding.is_equivalent_to(w, leaf.ndim), \\
            (leaf.shape, leaf.sharding, w)

    # ---- continuous decode HLO: logitshard stays vocab-gather-free -----
    V = cfg.vocab_size
    ag = hlo_stats.allgather_extent_count(
        emesh.continuous_decode_hlo(4, 24), V)
    assert ag == 0, f"continuous logitshard decode all-gathers vocab: {ag}"
    ebase = Engine(api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
                   bank=bank, ctx=ctx, logitshard=False)
    ag_b = hlo_stats.allgather_extent_count(
        ebase.continuous_decode_hlo(4, 24), V)
    assert ag_b >= 1, "replicated continuous baseline should gather logits"
    print("SUBPROC_CONT_OK")
""")


_SAMPLE_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.dist import context as dctx, sampling

    key = jax.random.PRNGKey(42)
    B, V = 8, 64
    lg = jax.random.normal(jax.random.PRNGKey(1), (B, V)) * 3.0

    # off-mesh reference stream
    dense = sampling.shard_sample(None, B, 0.8)
    want = np.asarray(dense(lg, key))

    # the SAME (key, row, vocab-id)-keyed noise field under two mesh
    # shapes: tokens must be bit-identical (reshard invariance)
    for shape in ((2, 4), (1, 8)):
        mesh = make_mesh(shape, ("data", "model"))
        ctx = dctx.make_ctx(mesh)
        fn = jax.jit(sampling.shard_sample(ctx, B, 0.8))
        got = np.asarray(fn(jax.device_put(lg, ctx.logits_sharding(B)), key))
        assert (got == want).all(), (shape, got, want)

    # temperature <= 0 degrades to the greedy shard_argmax
    g = sampling.shard_sample(None, B, 0.0)
    assert (np.asarray(g(lg, key))
            == np.asarray(jnp.argmax(lg, axis=-1))).all()

    # different keys give different samples (it IS sampling)
    k2 = jax.random.PRNGKey(43)
    assert (np.asarray(dense(lg, k2)) != want).any()

    # empirical frequency tracks softmax(logits/T): total variation small
    row = lg[:1]
    keys = jax.random.split(jax.random.PRNGKey(7), 2000)
    samp = jax.jit(jax.vmap(lambda k: dense(row, k)[0]))(keys)
    counts = np.bincount(np.asarray(samp), minlength=V) / 2000.0
    pref = np.asarray(jax.nn.softmax(row[0] / 0.8))
    tv = 0.5 * np.abs(counts - pref).sum()
    assert tv < 0.08, tv
    print("SUBPROC_SAMPLE_OK")
""")


def test_shard_sample_reshard_invariant_subprocess():
    """Gumbel-max temperature sampling: bit-identical token streams across
    mesh shapes and off-mesh (noise keyed on global coordinates), greedy
    degrade, and the empirical distribution matches softmax(logits/T)."""
    res = subprocess.run([sys.executable, "-c", _SAMPLE_TEST],
                         capture_output=True, text=True, timeout=900,
                         env=subproc_env())
    assert "SUBPROC_SAMPLE_OK" in res.stdout, res.stderr[-3000:]


_TOPP_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.dist import context as dctx, sampling

    key = jax.random.PRNGKey(42)
    B, V = 8, 64
    lg = jax.random.normal(jax.random.PRNGKey(1), (B, V)) * 3.0

    # off-mesh reference stream
    dense = sampling.shard_top_p(None, B, 0.9, temperature=0.8)
    want = np.asarray(dense(lg, key))

    # every cross-shard reduction in the nucleus selection is INTEGER
    # (fixed-point weights, histogram psum, scalar tie exchange), so the
    # kept set — and the sampled stream — is bit-identical across mesh
    # shapes and to the off-mesh path
    for shape in ((2, 4), (1, 8)):
        mesh = make_mesh(shape, ("data", "model"))
        ctx = dctx.make_ctx(mesh)
        fn = jax.jit(sampling.shard_top_p(ctx, B, 0.9, temperature=0.8))
        got = np.asarray(fn(jax.device_put(lg, ctx.logits_sharding(B)), key))
        assert (got == want).all(), (shape, got, want)

    # temperature <= 0 degrades to greedy (same (lg, key) signature)
    g = sampling.shard_top_p(None, B, 0.9, temperature=0.0)
    assert (np.asarray(g(lg, key))
            == np.asarray(jnp.argmax(lg, axis=-1))).all()

    # p -> 0 shrinks the nucleus to the single global max: exact argmax
    tiny = sampling.shard_top_p(None, B, 1e-6, temperature=0.8)
    assert (np.asarray(tiny(lg, key))
            == np.asarray(jnp.argmax(lg, axis=-1))).all()

    # different keys give different samples (it IS sampling)
    k2 = jax.random.PRNGKey(43)
    assert (np.asarray(dense(lg, k2)) != want).any()

    # every draw stays INSIDE the nucleus: at p=0.5 the sampled ids must
    # sit in the smallest softmax prefix covering 0.5 (+2 ranks of
    # fixed-point slack)
    z = np.asarray(lg, np.float64) / 0.8
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    order = np.argsort(-prob, axis=-1)
    half = sampling.shard_top_p(None, B, 0.5, temperature=0.8)
    for k in range(50):
        s = np.asarray(half(lg, jax.random.PRNGKey(k)))
        for b in range(B):
            c = np.cumsum(prob[b][order[b]])
            ncut = int(np.searchsorted(c, 0.5) + 1)
            assert s[b] in set(order[b][:ncut + 2]), (b, int(s[b]), ncut)

    # factory validates p
    try:
        sampling.shard_top_p(None, B, 0.0)
        raise SystemExit("p=0 accepted")
    except ValueError:
        pass
    print("SUBPROC_TOPP_OK")
""")


def test_shard_top_p_reshard_invariant_subprocess():
    """Shard-local nucleus sampling: bit-identical streams across mesh
    shapes and off-mesh (integer fixed-point threshold selection), greedy
    degrade at T<=0, argmax at p->0, and every draw inside the nucleus."""
    res = subprocess.run([sys.executable, "-c", _TOPP_TEST],
                         capture_output=True, text=True, timeout=900,
                         env=subproc_env())
    assert "SUBPROC_TOPP_OK" in res.stdout, res.stderr[-3000:]


def test_continuous_serving_subprocess():
    """Continuous batching on a (2,4) mesh: token-for-token equality with
    the host engine and the per-task lockstep oracle, cache_specs-exact
    post-admit shardings, and a vocab-gather-free continuous decode HLO."""
    res = subprocess.run([sys.executable, "-c", _CONT_TEST],
                         capture_output=True, text=True, timeout=900,
                         env=subproc_env())
    assert "SUBPROC_CONT_OK" in res.stdout, res.stderr[-3000:]
