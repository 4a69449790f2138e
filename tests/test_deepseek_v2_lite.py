"""DeepSeek-V2-Lite's mechanisms against the plain float32 reference
(``benchmarks/chip/references/deepseek_v2.py``, loaded by path, which
imports nothing of the program) at a tiny size on the CPU: latent attention
(MLA) with its absorbed decode, YaRN rope, a leading dense block, dropless
routed and shared experts, and the resident mixed-task decode step.

The program runs the configuration file ``deepseek-v2-lite-w4.json`` cut to
the CPU rehearsal's widths (``program_config``'s size keywords; the
reference's ``Arch.of`` takes the same) in float32, so the program and the
reference differ by the order of float32 sums alone.
"""
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import stale_rows_tokens

from repro.core import scale_bank as sb
from repro.kernels import ops
from repro.models import common, mla, moe, registry, transformer
from repro.serve import ServeConfig
from repro.train.serve import MOE_STEP_STATS, Engine, Request

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.append(str(CHIP))             # the reference imports ``weights``
SIZES = dict(n_layers=3, vocab=256, d_model=128, d_ff=256, heads=(4, 4))
SEED = 2 ** 31 + 15
# float32 throughout: the logits differ by the order of float32 sums, a few
# 1e-6 of logits of order 1; 1e-3 leaves room for that and for the
# absorbed decode's reassociation, and not for a dropped term
TOL = 1e-3


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"test_{kind}_{name}", CHIP / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    conf = json.loads((CHIP / "configs" / "deepseek-v2-lite-w4.json")
                      .read_text())
    ref = _load("references", "deepseek_v2")
    build = _load("builders", "deepseek_v2")
    cfg = build.program_config(conf, **SIZES).replace(dtype="float32")
    api = registry.build(cfg)
    params = build.make_params(cfg, SEED)
    arch = ref.Arch.of(conf, d=cfg.d_model, heads=cfg.n_heads,
                       kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
                       vocab=cfg.vocab_size, layers=cfg.n_layers)
    root = ref.weights.root_key(SEED)
    return cfg, api, params, ref, arch, root


def _task_scales(params, ref, arch, root, t: int):
    """Task ``t``'s scales, for the program (by leaf path) and the
    reference (by linear name): the backbone's times one factor a leaf."""
    base = ref.base_scales(arch, root)
    rng = np.random.default_rng(t)
    mult = {k: (1 + 0.05 * rng.standard_normal(v.shape)).astype(np.float32)
            for k, v in sorted(base.items())}
    prog = {f"layers/{k}/scale": np.asarray(base[k]) * mult[k] for k in base}
    return prog, {k: base[k] * mult[k] for k in base}


def test_yarn_frequencies_and_mscale():
    """YaRN as written out for DeepSeek-V2-Lite (θ 10000, 64 rope dims,
    factor 40, original context 4096, β_fast 32, β_slow 1): correction dims
    ⌊64·ln(4096/(32·2π)) / (2·ln 10⁴)⌋ = 10 and ⌈64·ln(4096/2π) /
    (2·ln 10⁴)⌉ = 23; below 10 the base frequency, above 23 it divided by
    40, a linear ramp between; temperature m = 0.1·0.707·ln 40 + 1."""
    from repro.configs.base import RopeScaling
    rs = RopeScaling(factor=40, original_max_position_embeddings=4096,
                     beta_fast=32, beta_slow=1, mscale=0.707,
                     mscale_all_dim=0.707)
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    want = []
    for i in range(32):
        base = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(base / 40 * ramp + base * (1 - ramp))
    got = np.asarray(common.yarn_freqs(10000.0, 64, rs))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(common.yarn_mscale(40, 0.707) - m) < 1e-12
    assert abs(m - 1.2608) < 1e-4
    ref = _load("references", "deepseek_v2")
    conf = json.loads((CHIP / "configs" / "deepseek-v2-lite-w4.json")
                      .read_text())
    arch = ref.Arch.of(conf)
    np.testing.assert_allclose(ref.yarn_inv_freq(arch), want, rtol=1e-6)
    assert abs(ref.softmax_scale(arch) - 192 ** -0.5 * m * m) < 1e-9


def test_prefill_then_decode_matches_reference(tiny):
    """Prefill of 7 tokens, then 5 decode steps through the latent cache:
    every position's logits are the reference's full forward pass's."""
    cfg, api, params, ref, arch, root = tiny
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (12,), 0,
                                         cfg.vocab_size), np.int32)
    p_len, total = 7, 12
    lg, pcache = api.prefill(params, {"tokens": jnp.asarray(toks[None,
                                                                 :p_len])})
    cache = jax.tree.map(
        lambda full, part: jax.lax.dynamic_update_slice_in_dim(
            full, part.astype(full.dtype), 0, axis=2),
        api.init_cache(1, total), pcache)
    got = [np.asarray(lg[0])]
    for t in range(p_len, total - 1):
        lg, cache = api.decode_step(params, cache,
                                    jnp.asarray(toks[None, t:t + 1]),
                                    jnp.int32(t))
        got.append(np.asarray(lg[0]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.served_logits(
            arch, root, jnp.asarray(toks), jnp.arange(p_len - 1, total - 1),
            ref.base_scales(arch, root)))
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=TOL)


def test_slotted_mixed_task_decode_matches_reference(tiny):
    """Three slots of three tasks decode in one slotted step per token
    (each slot at its own depth): each slot's logits are the reference's
    under its own task's scales, and the resident scheduler's tokens are
    those of per-task lockstep (drain) serving."""
    cfg, api, params, ref, arch, root = tiny
    sets = [_task_scales(params, ref, arch, root, t) for t in range(3)]
    base = sb.extract_scales(params, include_zero=True)
    stack = jax.tree.map(jnp.asarray, sb.stack_scales(
        base, [s for s, _ in sets]))
    rng = np.random.default_rng(3)
    lens = (5, 8, 3)
    seqs = [rng.integers(0, cfg.vocab_size, n + 4).astype(np.int32)
            for n in lens]
    cap = 16
    cache = api.init_cache(3, cap)
    tids = jnp.arange(3, dtype=jnp.int32)
    for b, (n, seq) in enumerate(zip(lens, seqs)):
        _, pc = api.prefill_slotted(params, stack,
                                    {"tokens": jnp.asarray(seq[None, :n])},
                                    tids[b:b + 1])
        cache = jax.tree.map(
            lambda full, part, b=b: jax.lax.dynamic_update_slice(
                full, part.astype(full.dtype), (0, b, 0, 0)), cache, pc)
    got = [[] for _ in lens]
    pos = np.asarray(lens, np.int32)
    for i in range(4):
        tok = np.asarray([s[n + i] for s, n in zip(seqs, lens)], np.int32)
        lg, cache, stats = api.decode_step_slotted(
            params, stack, cache, jnp.asarray(tok[:, None]),
            jnp.asarray(pos + i), tids)
        for b in range(3):
            got[b].append(np.asarray(lg[b]))
    assert int(stats[0]) == cfg.n_layers - cfg.n_dense_layers
    assert int(stats[2]) == (cfg.n_layers - 1) * 3 * cfg.moe.top_k
    for b, (n, seq) in enumerate(zip(lens, seqs)):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.served_logits(
                arch, root, jnp.asarray(seq), jnp.arange(n, n + 4),
                sets[b][1]))
        np.testing.assert_allclose(np.stack(got[b]), want, atol=TOL,
                                   rtol=TOL)

    bank = sb.ScaleBank()
    for t, (prog, _) in enumerate(sets):
        bank.tasks[f"t{t}"] = prog
    reqs = [Request(tokens=seq[:n], n_new=4, task=f"t{b}")
            for b, (n, seq) in enumerate(zip(lens, seqs))]
    # a fresh device copy each: drain's task swaps donate the live scales
    reports = {s: Engine(api, jax.tree.map(jnp.array, params),
                         bank=bank).serve(
        reqs, ServeConfig(n_slots=2, scheduler=s))
        for s in ("resident", "drain")}
    assert reports["resident"].scheduler == "resident"
    assert reports["resident"].tokens == reports["drain"].tokens
    assert reports["resident"].task_drain_idle_slot_steps == 0


def test_absorbed_latent_decode_matches_expanded(tiny):
    """Decode over the latent cache with W_kvb absorbed (each row under its
    own task's scales) gives the attention block's output that expanding
    K and V per head gives over the whole sequence."""
    cfg, api, params, ref, arch, root = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    t_n = 2
    stack = {name: {"scale": jnp.stack([lp[name]["scale"] * (1 + 0.1 * t)
                                        for t in range(t_n)]),
                    "zero": jnp.stack([lp[name]["zero"]] * t_n)}
             for name in ("wq", "wkv_a", "wkv_b", "wo")}
    b, s = 2, 9
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.d_model))
    tids = jnp.array([1, 0], jnp.int32)
    rows = jnp.repeat(tids, s)
    full, _, _ = mla._expanded(lp, x, cfg, jnp.arange(s), (rows, stack))
    cache = mla.init_cache(cfg, b, 16, n_layers=1)
    _, entry = mla.apply_prefill(lp, x[:, :s - 1], cfg,
                                 slots=(jnp.repeat(tids, s - 1), stack))
    cache = jax.tree.map(
        lambda c, e: c.at[0, :, :s - 1].set(e.astype(c.dtype)), cache, entry)
    out, cache = mla.apply_decode(lp, x[:, s - 1:], cfg, cache,
                                  jnp.int32(0),
                                  jnp.full((b,), s - 1, jnp.int32),
                                  slots=(tids, stack))
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), atol=TOL, rtol=TOL)
    assert cache["ckv"].shape[-1] == cfg.mla.kv_lora_rank


def _per_token(p, x, cfg):
    """Σ_k p_k E_k(x_t) + S(x_t) token by token, from dequantized weights."""
    spec = cfg.quant.spec()
    mc = cfg.moe
    deq = lambda lin: ops.dequantize_op(lin["qw"], lin["scale"], lin["zero"],
                                        lin["qw"].shape[-1] * spec.fields,
                                        spec, jnp.float32)

    def mlp(ws, v):
        return deq(ws["down"]) @ (jax.nn.silu(deq(ws["gate"]) @ v)
                                  * (deq(ws["up"]) @ v))
    out = []
    for v in np.asarray(x, np.float32).reshape(-1, cfg.d_model):
        probs = jax.nn.softmax(p["router"]["w"] @ v)
        top, idx = jax.lax.top_k(probs, mc.top_k)
        y = sum(float(w) * mlp(jax.tree.map(lambda a: a[int(e)],
                                            p["experts_ep"]), v)
                for w, e in zip(top, idx))
        out.append(y + mlp(p["shared"], v))
    return np.stack(out).reshape(x.shape)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("skew", [False, True])
def test_dropless_routing_matches_per_token_loop(tiny, impl, skew):
    """Every token's top k experts run however uneven the load: the grouped
    block (the plain path, and the Pallas kernel in interpret mode) equals
    a per-token loop, also when every token routes to expert 3."""
    cfg, api, params, ref, arch, root = tiny
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 11, cfg.d_model))
    if skew:
        u = jnp.ones((cfg.d_model,)) / cfg.d_model ** 0.5
        x = x * 0.3 + 3 * u
        p = {**p, "router": {"w": p["router"]["w"].at[3].set(8 * u)}}
    with ops.force_impl(impl):
        y, _, stats = moe.apply(p, x, cfg)
    n = x.shape[0] * x.shape[1]
    assert int(stats[1]) == n * cfg.moe.top_k          # nothing dropped
    if skew:
        assert int(stats[2]) == n                      # expert 3: every row
    np.testing.assert_allclose(np.asarray(y), _per_token(p, x, cfg),
                               atol=TOL, rtol=TOL)


def test_registry_serves_mla_moe_on_the_resident_path(tiny):
    """The config reaches the resident scheduler: slotted prefill and decode
    exist, and the slot pool holds the latent cache."""
    cfg, api, params = tiny[:3]
    assert api.caps.slotted_reason is None
    assert api.decode_step_slotted is not None
    assert api.prefill_slotted is not None
    eng = Engine(api, params)
    pool = eng.open_pool(2, 16)
    assert set(pool.cache) == {"ckv", "kpe"}
    bdims, sdims = eng._cache_dims()
    assert bdims == {"ckv": 1, "kpe": 1} and sdims == {"ckv": 2, "kpe": 2}
    assert MOE_STEP_STATS[0] == "moe_layers"
    assert transformer.n_dense(cfg) == 1


def test_latent_rows_past_pos_never_read(tiny):
    """The latent pool (``ckv``, ``kpe``) under the resident pool: rows an
    evicted request left past the new occupant's position, and every other
    row at or past a slot's ``pos``, change no token when poisoned
    (``conftest.stale_rows_tokens``)."""
    cfg, api, params, ref, arch, root = tiny
    bank = sb.ScaleBank()
    for t in range(2):
        bank.tasks[f"t{t}"] = _task_scales(params, ref, arch, root, t)[0]
    eng = Engine(api, jax.tree.map(jnp.array, params), bank=bank)
    eng.resident = sb.ResidentStack(bank, eng.params, 2)
    pool = eng.open_pool(2, 24)
    pool.slotted = True
    assert set(pool.cache) == {"ckv", "kpe"}
    clean, poisoned = stale_rows_tokens(eng, pool, ("t0", "t1"))
    assert clean == poisoned


def test_mesh_refusal_reads_the_caps(tiny):
    """Under a mesh the engine keeps MoE off the resident path with the
    reason its capability record gives, and tests no family itself."""
    from repro.dist import context as dctx
    from repro.launch.mesh import make_mesh
    cfg, api, params = tiny[:3]
    assert api.caps.mesh_slotted_reason is not None
    dense = registry.build(cfg.replace(moe=None, mla=None))
    assert dense.caps.mesh_slotted_reason is None
    assert Engine(api, params)._slotted_reason() is None
    ctx = dctx.make_ctx(make_mesh((1, 1), ("data", "model")))
    eng = Engine(api, params, ctx=ctx)
    assert eng._slotted_reason() == api.caps.mesh_slotted_reason


@pytest.mark.parametrize("layout,group,n_tasks", [
    ("nibble", None, 0), ("nibble", 32, 3), ("plane", None, 2)])
def test_grouped_kernel_matches_plain_path(layout, group, n_tasks):
    """The grouped kernel (interpret mode) against the plain ``ragged_dot``
    path on uneven groups, one expert untouched and tiles left unused: per
    group scales, bit planes and per-row task scales included.  Both take
    the same bf16-exact integer codes, so they differ by float32 sums."""
    from repro.core import quant
    from repro.core.quant import QuantSpec
    from repro.kernels import quant_matmul as qm
    spec = QuantSpec(bits=3 if layout == "plane" else 4, group_size=group,
                     layout=layout)
    e, n, k, bm = 4, 128, 256, 16
    sizes = np.array([32, 0, 16, 48], np.int32)           # expert 1 idle
    rows = int(sizes.sum())
    m = rows + 2 * bm                                     # 2 unused tiles
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 1 << spec.bits, (e, n, k)).astype(np.uint32)
    pack = (jax.vmap(lambda q: quant.pack_codes_planes(q, spec.bits))
            if layout == "plane" else jax.vmap(quant.pack_codes))
    qw = pack(jnp.asarray(codes))
    g = spec.n_groups(k)
    shape = (e, n_tasks, n, g) if n_tasks else (e, n, g)
    scale = jnp.asarray(rng.uniform(0.01, 0.02, shape), jnp.float32)
    zero = jnp.asarray(rng.uniform(3, 5, shape), jnp.float32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    ends = np.cumsum(sizes)
    tile_expert = np.minimum(np.searchsorted(
        ends, np.minimum(np.arange(m // bm) * bm, ends[-1] - 1),
        side="right"), e - 1).astype(np.int32)
    tids = jnp.asarray(rng.integers(0, max(n_tasks, 1), m), jnp.int32)
    got = qm.quant_gmm_pallas(
        x, qw, scale, zero, jnp.asarray(tile_expert),
        jnp.int32(rows // bm), spec=spec, block_m=bm,
        task_ids=tids if n_tasks else None, interpret=True)
    want = ops.grouped_quant_matmul(
        x, qw, scale, zero, jnp.asarray(sizes), jnp.asarray(tile_expert),
        jnp.int32(rows // bm), spec, block_m=bm,
        task_ids=tids if n_tasks else None, impl="xla")
    np.testing.assert_allclose(np.asarray(got)[:rows],
                               np.asarray(want)[:rows], rtol=1e-4,
                               atol=1e-4)
