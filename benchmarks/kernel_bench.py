"""Kernel-level benchmarks (paper §3.3 deployment claims, TPU-adapted).

  * HBM-traffic model for the fused dequant-matmul: bytes moved per GEMV
    at W16 / W4 / W3 vs activation bytes — the memory-boundedness argument.
  * CPU wall-time sanity of the jitted XLA paths (quantized vs fp matmul).
  * Task-switch latency: ScaleBank swap vs full-model reload (paper's
    "fast task switching" row of Table 1).
  * Sharded serving: per-shard ScaleBank swaps + shard-local logitshard
    sampling on a (data, model) mesh — bytes moved and wall time vs the
    replicated baseline, plus the HLO guards the serve-smoke CI job runs
    (``python -m benchmarks.kernel_bench --check-sharded`` exits non-zero
    on any sharding problem, swap resharding collective, or vocab
    all-gather in the logitshard decode step).
  * Continuous batching: a mixed-length workload (n_new ∈ {8, 32, 128})
    through the paged slot-pool engine vs the lockstep baseline —
    tokens/s, decode-step and bubble-slot-step counts, plus the continuous
    guards ``--check-sharded`` enforces: zero bubbles, ≥1.5× fewer decode
    steps, post-admit cache shardings == ``cache_specs``, and zero
    vocab-extent all-gathers in the continuous decode HLO.
  * GEMV roofline: the analytic bytes/token model of the fused dequant
    GEMV (each packed word streamed from HBM exactly once — checked
    against the kernel's grid arithmetic) and its ratio over an fp16
    GEMV; ``--check-sharded`` gates the 4-bit nibble ratio ≥ 3.2×, the
    3-bit BIT-PLANE ratio ≥ 4.2×, and plane-vs-nibble decode
    weight-bytes/token ≥ 1.25× (sub-4-bit finally pays in bytes).
  * Mixed-task serving: 3 tasks round-robin through ``Engine.serve``
    under both schedulers; gates token-for-token equality, ZERO
    task-drain idle slot-steps under ``resident`` (>0 under ``drain``),
    and ≥ 1.2× fewer decode steps — all deterministic counters, so a
    noisy runner cannot flake the build.  Wall-clock tokens/s is
    reported unguarded.
  * Family serving: one tiny arch per served family (dense, encdec, vlm,
    ssm, hybrid) through the SAME continuous-batching slot pool — gates
    token-for-token equality with lockstep and zero bubble slot-steps
    per family (the slot-state protocol matrix, docs/SERVING.md).
  * Production serving: seeded Poisson / trace-replay traffic through the
    event-driven admission loop (``repro.serve``), both schedulers, with
    per-request SLO percentiles (TTFT/TPOT/queue-wait/e2e on the virtual
    clock — deterministic, trajectory-guarded), a same-seed determinism
    gate, and an overload arm that must SHED (bounded queue, every
    request accounted served/rejected/shed, served tokens identical to
    the unloaded run).
  * ScaleBank tiering: 10k on-disk tasks opened LAZILY (gate: zero
    payload bytes deserialized at init) and served zipfian through the
    resident scheduler with nonzero virtual tier costs — gates token
    equality with the eagerly-warmed bank, resident-hit swap p99 under
    one decode ``step_s``, and a majority of admits landing device/host
    (the admission-loop prefetcher doing its job).

``--emit-json DIR`` writes the structured metrics (schema:
``repro.serve.telemetry``) to ``DIR/BENCH_kernels.json`` and
``DIR/BENCH_serving.json`` — the CI jobs upload both as build artifacts
and ``benchmarks/trajectory.py`` diffs the guarded rows against the
committed baselines.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.core import scale_bank as sb
from repro.core.quant import QTensor, QuantSpec
from repro.core.scale_bank import ScaleBank
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.serve import telemetry


# structured metrics, populated alongside the human report lines and
# dumped by --emit-json in the repro.serve.telemetry schema; "serving"
# rows land in BENCH_serving.json, everything else in BENCH_kernels.json.
# Wall-clock rows are marked wall=True (excluded from reproducibility
# diffs); guard=(direction, band) rows are what trajectory.py gates.
SINK = telemetry.MetricSink()
RUN_META: dict = {}      # generating parameters, stamped into the "run" block


def metric(name: str, value, unit: str = "", *, wall: bool = False,
           guard=None, **extra):
    SINK.log(name, value, unit, wall=wall, guard=guard, **extra)


def emit_json(outdir: str):
    import os
    os.makedirs(outdir, exist_ok=True)
    serving_keys = ("sharded", "logitshard", "continuous", "mixed_task",
                    "serving")
    rows = SINK.metrics
    kern = [m for m in rows if not any(k in m["name"] for k in serving_keys)]
    serv = [m for m in rows if any(k in m["name"] for k in serving_keys)]
    for fname, entries in (("BENCH_kernels.json", kern),
                           ("BENCH_serving.json", serv)):
        path = os.path.join(outdir, fname)
        SINK.write(path, entries, **RUN_META)
        print(f"[emit-json] wrote {path} ({len(entries)} metrics)")


def _time(fn, *args, n=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def traffic_model(report):
    """Per-token GEMV bytes for a LLaMA-7B layer stack (analytic)."""
    L, d, _, d_ff, vocab = configs.PAPER_MODELS["llama-7b"]
    n_matrix = L * (4 * d * d + 3 * d * d_ff)
    act = L * 7 * d * 2  # bf16 activations in/out per linear (negligible)
    for name, bits in (("w16", 16), ("w4", 4), ("w3", 3)):
        wb = n_matrix * bits / 8
        report(f"kernel/traffic_{name}", 0.0,
               f"weight_bytes_per_token={wb / 1e9:.2f}GB "
               f"speedup_vs_fp16={16 / bits:.2f}x (memory-bound regime)")


def gemv_roofline(report, check: bool = False) -> bool:
    """Analytic bytes/token of the fused dequant GEMV + single-stream check.

    The decode GEMV is memory-bound: per token each packed weight word
    crosses HBM exactly ONCE (grid (N/bn,), each step one whole K row of
    words; the qw BlockSpec tiles the word array disjointly — checked
    below against the kernel's own block arithmetic), plus one pass over the (N, G) scale/zero rows.
    4-bit weights therefore move ~4/16 of the fp16 bytes; the gate
    requires ≥ 3.2× including the scale overhead at group 128.

    Layouts: NIBBLE packing (PACK = 8/word) stores 3-bit codes in 4-bit
    slots, so sub-4-bit saves quantization levels, not decode bytes.
    BIT-PLANE packing (PLANE_PACK = 32 codes/word/plane, b planes) stores
    exactly b/8 bytes per weight — 3-bit truly moves 3/8 B/weight.  The
    gates require the 3-bit plane ratio ≥ 4.2× vs fp16 and ≥ 1.25× fewer
    decode weight-bytes/token than the nibble layout.
    """
    from repro.kernels.quant_matmul import DEFAULT_BLOCK_N, PACK, PLANE_PACK
    from repro.kernels import quant_matmul as qm
    from repro.kernels import ref as kref
    from repro.core.quant import QuantSpec

    ok = True
    L, d, _, d_ff, vocab = configs.PAPER_MODELS["llama-7b"]
    group = 128
    for name, (nn, kk) in (("attn_proj", (d, d)), ("mlp_up", (d_ff, d)),
                           ("mlp_down", (d, d_ff))):
        g = kk // group
        qw_b = nn * kk // PACK * 4          # uint32 words, streamed once
        sz_b = 2 * nn * g * 4               # f32 scale + zero rows
        act_b = (kk + nn) * 2               # bf16 x in / y out
        q_total = qw_b + sz_b + act_b
        fp16_b = nn * kk * 2 + act_b
        ratio = fp16_b / q_total

        # single-stream invariant from the kernel's own block arithmetic:
        # the (N/bn,) grid loads bn*K/PACK words per step, disjoint
        # tiles, so total word-loads must equal the word count exactly
        bn = min(DEFAULT_BLOCK_N, nn)
        bk = kk
        if nn % bn or kk % bk:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL blocks ({bn},{bk}) do not tile ({nn},{kk})")
            ok = False
            continue
        loads = (nn // bn) * (kk // bk) * (bn * bk // PACK)
        single = loads == nn * kk // PACK
        if not single:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL qw not single-stream: {loads} word-loads for "
                   f"{nn * kk // PACK} words")
            ok = False
        if check and ratio < 3.2:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL bytes/token ratio {ratio:.2f}x < 3.2x")
            ok = False

        # bit-plane layout: 3 planes of K/32-word rows — w3 moves 3/8
        # B/weight for real (nibble w3 still moves 4/8), same scale rows
        qw3_b = 3 * nn * (kk // PLANE_PACK) * 4
        q3_total = qw3_b + sz_b + act_b
        ratio3 = fp16_b / q3_total
        plane_vs_nibble = q_total / q3_total
        bk3 = kk
        loads3 = (nn // bn) * (kk // bk3) * (3 * bn * bk3 // PLANE_PACK)
        single3 = loads3 == 3 * nn * kk // PLANE_PACK
        if not single3:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL plane qw not single-stream: {loads3} word-loads "
                   f"for {3 * nn * kk // PLANE_PACK} words")
            ok = False
        if check and ratio3 < 4.2:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL 3-bit plane bytes/token ratio {ratio3:.2f}x "
                   f"< 4.2x vs fp16")
            ok = False
        if check and plane_vs_nibble < 1.25:
            report(f"kernel/gemv_roofline_{name}", 0.0,
                   f"FAIL 3-bit plane moves only {plane_vs_nibble:.2f}x "
                   f"fewer decode weight-bytes/token than nibble (< 1.25x)")
            ok = False

        report(f"kernel/gemv_roofline_{name}", 0.0,
               f"bytes/token w4_nibble={q_total / 1e6:.2f}MB "
               f"w3_plane={q3_total / 1e6:.2f}MB fp16="
               f"{fp16_b / 1e6:.2f}MB ratio={ratio:.2f}x/{ratio3:.2f}x "
               f"plane_vs_nibble={plane_vs_nibble:.2f}x "
               f"single_stream={single}/{single3}")
        metric(f"kernel/gemv_roofline_{name}", ratio, "x_vs_fp16",
               guard=("higher", 0.15),
               bytes_per_token_w4=q_total, bytes_per_token_fp16=fp16_b,
               single_stream=bool(single), block_n=bn, block_k=bk)
        metric(f"kernel/gemv_roofline_plane3_{name}", ratio3, "x_vs_fp16",
               guard=("higher", 0.15),
               bytes_per_token_w3_plane=q3_total,
               plane_vs_nibble=plane_vs_nibble,
               single_stream=bool(single3), block_n=bn, block_k=bk3)
        metric(f"kernel/gemv_plane_bytes_ratio_{name}", plane_vs_nibble,
               "x_vs_nibble", guard=("higher", 0.1))

    # sanity: the GEMV kernel (interpret mode) agrees with the
    # blocked-replay oracle within the f32 summation-order bound at a small
    # shape — the full sweep lives in tests/test_gemv.py; this keeps the
    # bench self-checking
    rng = np.random.default_rng(0)
    m, n, k, grp = 4, 128, 256, 64
    spec = QuantSpec(bits=4, group_size=grp)
    qw = jnp.asarray(rng.integers(0, 2 ** 32, size=(n, k // PACK),
                                  dtype=np.uint32))
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (n, k // grp)).astype(np.float32))
    zero = jnp.asarray(rng.uniform(0, 15, (n, k // grp)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    got = qm.quant_gemv_pallas(x, qw, scale, zero, spec=spec, interpret=True)
    want = kref.quant_gemv_ref(x, qw, scale, zero, (n, k), spec)
    tol = kref.f32_order_tolerance(x, qw, scale, zero, (n, k), spec)
    exact = bool(np.all(np.abs(np.asarray(got) - np.asarray(want))
                        <= np.asarray(tol)))
    if not exact:
        report("kernel/gemv_bitexact", 0.0, "FAIL interpret GEMV != oracle")
        ok = False
    else:
        report("kernel/gemv_bitexact", 0.0,
               f"interpret GEMV == oracle within the f32 order bound at "
               f"({m},{n},{k},g{grp})")
    metric("kernel/gemv_bitexact", int(exact), "bool",
           guard=("higher", 0.0))
    return ok


def xla_path_walltime(report):
    rng = np.random.default_rng(0)
    for (m, n, k) in ((1, 4096, 4096), (16, 4096, 4096)):
        w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.02)
        spec = QuantSpec(bits=4)
        qt = QTensor.quantize(w, spec, n_grid=2)
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))

        fp = jax.jit(lambda x, w: x @ w.T)
        qx = jax.jit(lambda x: ops.quant_matmul(x, qt.qw, qt.scale, qt.zero,
                                                spec, impl="xla"))
        t_fp = _time(fp, x, w)
        t_q = _time(qx, x)
        report(f"kernel/xla_m{m}", t_q,
               f"quant={t_q:.0f}us fp={t_fp:.0f}us (CPU sanity; the "
               f"bandwidth win is a TPU/HBM effect — see traffic model)")


def task_switch(report):
    cfg = configs.paper_lm(n_layers=4, d_model=256, n_heads=4, d_ff=512,
                           vocab=512).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    bank = ScaleBank()
    bank.add("A", p)
    pB = jax.tree_util.tree_map_with_path(
        lambda kp, l: l * 1.01 if str(getattr(kp[-1], "key", "")) == "scale"
        else l, p)
    bank.add("B", pB)

    t0 = time.perf_counter()
    for i in range(10):
        p = bank.switch(p, "B" if i % 2 == 0 else "A")
    jax.block_until_ready(p)      # every swapped leaf — honest wall time
    t_switch = (time.perf_counter() - t0) / 10 * 1e6

    # full reload = re-device_put the whole tree
    host = jax.tree.map(np.asarray, p)
    t0 = time.perf_counter()
    for _ in range(10):
        p2 = jax.tree.map(jnp.asarray, host)
    jax.block_until_ready(p2)
    t_reload = (time.perf_counter() - t0) / 10 * 1e6

    total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p))
    report("kernel/task_switch", t_switch,
           f"scale_swap={t_switch:.0f}us full_reload={t_reload:.0f}us "
           f"payload={bank.nbytes('A')}B of {total}B model "
           f"({100 * bank.nbytes('A') / total:.1f}%)")
    metric("kernel/task_switch", t_switch, "us", wall=True,
           full_reload_us=t_reload, swap_payload_bytes=bank.nbytes("A"),
           model_bytes=total)


def _serving_cfg():
    # vocab must equal NO other extent in the decode HLO: the CI gate
    # counts all-gathers by the vocab extent, so a d_ff == vocab collision
    # would let an activation regather masquerade as a logit gather
    return configs.paper_lm(n_layers=4, d_model=256, n_heads=4, d_ff=384,
                            vocab=512).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))


def sharded_serving(report, check: bool = False) -> bool:
    """Mesh-native serving microbenchmark + HLO guards.

    Needs ≥ 2 devices (CI fakes 8 CPU devices via XLA_FLAGS); on a single
    device it reports a skip — except in check mode, where a missing mesh
    means the CI env is broken and must fail loudly.
    """
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.launch import hlo_stats
    from repro.train.serve import Engine

    n = jax.device_count()
    if n < 2:
        report("kernel/sharded_swap", 0.0,
               "skipped: 1 device (set XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")
        return not check
    model = 4 if n % 4 == 0 else 2
    mesh = make_mesh((n // model, model), ("data", "model"))
    ctx = dctx.make_ctx(mesh)

    cfg = _serving_cfg()
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    # host snapshot: device trees below are donated on swap, and device_put
    # may alias a source buffer that lives on a target device — every
    # device tree must be built from its own host copy
    p = jax.tree.map(np.asarray, p)
    bank = ScaleBank()
    bank.add("A", p)
    bank.add("B", jax.tree_util.tree_map_with_path(
        lambda kp, l: l * 1.01 if str(getattr(kp[-1], "key", "")) == "scale"
        else l, p))

    ok = True
    problems = shard_rules.validate_for_mesh(p, mesh)
    if problems:
        report("kernel/sharded_swap", 0.0,
               f"FAIL sharding_problems={problems[:3]}")
        ok = False

    sp = jax.device_put(p, shard_rules.named_shardings(ctx, p))
    hlo = sb.swap_hlo(sp, bank.tasks["B"], ctx)
    coll = hlo_stats.collective_stats(hlo)
    if coll["total_bytes"] > 0:
        report("kernel/sharded_swap_hlo", 0.0,
               f"FAIL resharding collectives in swap HLO: {coll}")
        ok = False

    # sharded swap: warm the install jit, then time alternating swaps,
    # blocking on the WHOLE tree (honest wall time)
    sp = bank.switch(sp, "A", ctx=ctx, donate=True)
    jax.block_until_ready(sp)
    t0 = time.perf_counter()
    for i in range(10):
        sp = bank.switch(sp, "B" if i % 2 == 0 else "A", ctx=ctx, donate=True)
    jax.block_until_ready(sp)
    t_shard = (time.perf_counter() - t0) / 10 * 1e6

    # replicated baseline: the pre-mesh host path on a single-device tree
    rp = jax.tree.map(jnp.array, p)
    rp = bank.switch(rp, "A")
    jax.block_until_ready(rp)
    t0 = time.perf_counter()
    for i in range(10):
        rp = bank.switch(rp, "B" if i % 2 == 0 else "A")
    jax.block_until_ready(rp)
    t_repl = (time.perf_counter() - t0) / 10 * 1e6

    local_b, total_b = bank.local_nbytes("A", ctx), bank.nbytes("A")
    report("kernel/sharded_swap", t_shard,
           f"sharded={t_shard:.0f}us replicated={t_repl:.0f}us "
           f"bytes/device={local_b}B of {total_b}B "
           f"({n // model}x{model} mesh, no swap collectives: "
           f"{coll['total_bytes'] == 0})")
    metric("kernel/sharded_swap", t_shard, "us", wall=True,
           replicated_us=t_repl, bytes_per_device=local_b,
           total_bytes=total_b,
           swap_collective_bytes=coll["total_bytes"])

    # shard-local sampler: logitshard decode must contain NO vocab-extent
    # all-gather; the replicated baseline shows the one it deletes
    mk = lambda ls: Engine(
        api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
        bank=bank, ctx=ctx, logitshard=ls)
    eng_base, eng_ls = mk(False), mk(True)
    b, cache_len, vocab = 4, 32, cfg.vocab_size
    ag_base = hlo_stats.allgather_extent_count(
        eng_base.decode_hlo(b, cache_len), vocab)
    ag_ls = hlo_stats.allgather_extent_count(
        eng_ls.decode_hlo(b, cache_len), vocab)
    if ag_ls:
        report("kernel/logitshard_hlo", 0.0,
               f"FAIL {ag_ls} vocab all-gathers in logitshard decode")
        ok = False

    prompt = jax.device_put(
        jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (b, 1)),
        ctx.sharding())
    times = {}
    for name, eng in (("replicated", eng_base), ("logitshard", eng_ls)):
        jax.block_until_ready(eng.generate(prompt, n_new=8))   # compile+sync
        t0 = time.perf_counter()
        jax.block_until_ready(eng.generate(prompt, n_new=8))
        times[name] = (time.perf_counter() - t0) / 8 * 1e6
    report("kernel/logitshard_sample", times["logitshard"],
           f"decode+sample logitshard={times['logitshard']:.0f}us/tok "
           f"replicated={times['replicated']:.0f}us/tok "
           f"vocab_allgathers: baseline={ag_base} logitshard={ag_ls}")
    return ok


def continuous_serving(report, check: bool = False) -> bool:
    """Continuous batching vs lockstep on a mixed-length workload.

    The lockstep baseline serves n_slots-sized batches in arrival order,
    decoding every batch to its LONGEST member — short sequences pay
    bubble slot-steps.  The continuous engine admits/evicts mid-loop at
    one compiled shape, so every decode step serves only live sequences.
    Same guard policy as ``sharded_serving``: on one device this is a
    skip, except in check mode.  Wall-clock tokens/s is reported; the CI
    gate checks the DETERMINISTIC invariants (step counts, bubbles,
    shardings, HLO) so a noisy runner cannot flake the build.
    """
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.launch import hlo_stats
    from repro.train.serve import Engine, Request

    n = jax.device_count()
    if n < 2:
        report("kernel/continuous", 0.0,
               "skipped: 1 device (set XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")
        return not check
    model = 4 if n % 4 == 0 else 2
    mesh = make_mesh((n // model, model), ("data", "model"))
    ctx = dctx.make_ctx(mesh)

    cfg = _serving_cfg()
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)
    vocab = cfg.vocab_size

    n_slots, lengths = 4, (8, 32, 128)
    reqs = [Request(tokens=(np.arange(8, dtype=np.int32) * (i + 1)) % vocab,
                    n_new=lengths[i % len(lengths)])
            for i in range(3 * n_slots)]
    tokens_total = sum(r.n_new for r in reqs)
    groups = [reqs[i:i + n_slots] for i in range(0, len(reqs), n_slots)]
    lock_steps = sum(max(r.n_new for r in g) - 1 for g in groups)
    lock_bubbles = sum(max(r.n_new for r in g) - r.n_new
                       for g in groups for r in g)

    mk = lambda: Engine(
        api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
        ctx=ctx, logitshard=True)
    eng = mk()
    ok = True

    # ---- lockstep baseline: batch by arrival order, decode to the max
    lock_out = []
    for g in groups:                                    # compile warmup
        eng.generate(jnp.asarray(np.stack([r.tokens for r in g])),
                     n_new=max(r.n_new for r in g))
    t0 = time.perf_counter()
    for g in groups:
        out = eng.generate(jnp.asarray(np.stack([r.tokens for r in g])),
                           n_new=max(r.n_new for r in g))
        lock_out.append(np.asarray(out))
    t_lock = time.perf_counter() - t0

    # ---- continuous: paged slots, mid-loop admit/evict ------------------
    from repro.serve import ServeConfig
    eng2 = mk()
    eng2.serve(reqs, ServeConfig(n_slots=n_slots))      # compile warmup
    rep = eng2.serve(reqs, ServeConfig(n_slots=n_slots))
    if rep.bubble_slot_steps != 0:
        report("kernel/continuous", 0.0,
               f"FAIL {rep.bubble_slot_steps} bubble slot-steps")
        ok = False
    step_ratio = lock_steps / max(rep.steps, 1)
    if check and step_ratio < 1.5:
        report("kernel/continuous", 0.0,
               f"FAIL step ratio {step_ratio:.2f}x < 1.5x "
               f"(lockstep {lock_steps} vs continuous {rep.steps})")
        ok = False

    # correctness: continuous output == the lockstep rows, per request
    for i, r in enumerate(reqs):
        row = lock_out[i // n_slots][i % n_slots]
        want = row[len(r.tokens):len(r.tokens) + r.n_new]
        if rep.tokens[i] is None or not np.array_equal(
                np.asarray(rep.tokens[i]), want):
            report("kernel/continuous", 0.0,
                   f"FAIL req{i} tokens diverge from lockstep")
            ok = False
            break

    # post-admit slot-pool shardings == cache_specs
    pool = eng2.open_pool(n_slots, 64)
    eng2.admit(pool, Request(tokens=np.arange(8, dtype=np.int32), n_new=4))
    want_sh = eng2._cache_shardings(pool.cache, n_slots)
    for leaf, w in zip(jax.tree.leaves(pool.cache),
                       jax.tree.leaves(want_sh)):
        if not leaf.sharding.is_equivalent_to(w, leaf.ndim):
            report("kernel/continuous", 0.0,
                   f"FAIL post-admit sharding {leaf.sharding} != {w}")
            ok = False
            break

    # continuous decode HLO: still zero vocab-extent all-gathers
    ag = hlo_stats.allgather_extent_count(
        eng2.continuous_decode_hlo(n_slots, 64), vocab)
    if ag:
        report("kernel/continuous_hlo", 0.0,
               f"FAIL {ag} vocab all-gathers in continuous decode")
        ok = False

    report("kernel/continuous", rep.wall_s * 1e6,
           f"tok/s continuous={tokens_total / rep.wall_s:.0f} "
           f"lockstep={tokens_total / t_lock:.0f} "
           f"({tokens_total / rep.wall_s / (tokens_total / t_lock):.2f}x) "
           f"steps={rep.steps} vs {lock_steps} ({step_ratio:.2f}x) "
           f"bubbles={rep.bubble_slot_steps} vs {lock_bubbles} "
           f"idle={rep.idle_slot_steps} vocab_allgathers={ag}")
    metric("kernel/continuous", tokens_total / rep.wall_s, "tok/s",
           wall=True,
           lockstep_tok_s=tokens_total / t_lock, steps=rep.steps,
           lockstep_steps=lock_steps, step_ratio=step_ratio,
           bubble_slot_steps=rep.bubble_slot_steps,
           idle_slot_steps=rep.idle_slot_steps)
    # deterministic step-count win: the trajectory-gated view of the same
    # speedup (wall tok/s is machine noise; this is not)
    metric("kernel/continuous_step_ratio", step_ratio, "x_vs_lockstep",
           guard=("higher", 0.15))
    # tokens/s win as a SELF-NORMALIZED same-run ratio: machine-independent
    # enough to gate, wall-marked because both numerators are timings
    metric("kernel/continuous_tok_ratio", t_lock / rep.wall_s,
           "x_vs_lockstep", wall=True, guard=("higher", 0.15))
    return ok


def mixed_task_serving(report, check: bool = False) -> bool:
    """Drain-free mixed-task decode: ``resident`` vs ``drain`` scheduler.

    3 tasks round-robin over 12 requests with cycling budgets; both
    schedulers run from fresh engines built off the SAME host snapshot.
    Deterministic gates (check mode): token-for-token equality, zero
    task-drain idle slot-steps under ``resident`` (positive under
    ``drain``), and ≥ 1.2× fewer decode steps.  Runs on the fake-device
    mesh when available (exercising the stacked-scale shardings), off-mesh
    otherwise — the counters are identical either way.
    """
    from repro.dist import context as dctx
    from repro.dist import sharding as shard_rules
    from repro.train.serve import Engine, Request

    cfg = _serving_cfg()
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)
    vocab = cfg.vocab_size

    bank = ScaleBank()
    bank.add("t0", p)
    rngs = np.random.default_rng(7)
    for t in ("t1", "t2"):
        bank.tasks[t] = {k: (v * rngs.uniform(0.8, 1.2, v.shape)
                             ).astype(v.dtype)
                         for k, v in bank.tasks["t0"].items()}
    tasks = ("t0", "t1", "t2")
    reqs = [Request(tokens=(np.arange(6, dtype=np.int32) * (i + 1)) % vocab,
                    n_new=(6, 12, 24)[i % 3], task=tasks[i % 3])
            for i in range(12)]
    tokens_total = sum(r.n_new for r in reqs)

    n = jax.device_count()
    if n >= 2:
        model = 4 if n % 4 == 0 else 2
        mesh = make_mesh((n // model, model), ("data", "model"))
        ctx = dctx.make_ctx(mesh)
        mk = lambda: Engine(
            api, jax.device_put(p, shard_rules.named_shardings(ctx, p)),
            bank=bank, ctx=ctx, logitshard=True)
    else:
        ctx = None
        mk = lambda: Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)

    from repro.serve import ServeConfig
    ok = True
    reports = {}
    for sched in ("drain", "resident"):
        cfg_s = ServeConfig(n_slots=4, scheduler=sched)
        eng = mk()
        eng.serve(reqs, cfg_s)                            # compile warmup
        eng = mk()
        reports[sched] = eng.serve(reqs, cfg_s)
    rd, rr = reports["drain"], reports["resident"]

    for i, (a, b) in enumerate(zip(rd.tokens, rr.tokens)):
        if a is None or a != b:
            report("kernel/mixed_task", 0.0,
                   f"FAIL req{i}: resident tokens diverge from drain")
            ok = False
            break
    if rr.task_drain_idle_slot_steps != 0:
        report("kernel/mixed_task", 0.0,
               f"FAIL resident task_drain_idle_slot_steps="
               f"{rr.task_drain_idle_slot_steps} (must be 0)")
        ok = False
    if rd.task_drain_idle_slot_steps <= 0:
        report("kernel/mixed_task", 0.0,
               "FAIL drain scheduler shows no task-drain idle (workload "
               "not exercising the drain tax?)")
        ok = False
    step_ratio = rd.steps / max(rr.steps, 1)
    if check and step_ratio < 1.2:
        report("kernel/mixed_task", 0.0,
               f"FAIL step ratio {step_ratio:.2f}x < 1.2x "
               f"(drain {rd.steps} vs resident {rr.steps})")
        ok = False

    report("kernel/mixed_task", rr.wall_s * 1e6,
           f"tok/s resident={tokens_total / rr.wall_s:.0f} "
           f"drain={tokens_total / rd.wall_s:.0f} "
           f"steps={rr.steps} vs {rd.steps} ({step_ratio:.2f}x) "
           f"task_drain_idle={rr.task_drain_idle_slot_steps} vs "
           f"{rd.task_drain_idle_slot_steps} "
           f"switches={rr.switches} vs {rd.switches} "
           f"installs={rr.resident_installs}")
    metric("kernel/mixed_task", tokens_total / rr.wall_s, "tok/s",
           wall=True,
           drain_tok_s=tokens_total / rd.wall_s,
           resident_steps=rr.steps, drain_steps=rd.steps,
           step_ratio=step_ratio,
           resident_task_drain_idle=rr.task_drain_idle_slot_steps,
           drain_task_drain_idle=rd.task_drain_idle_slot_steps,
           resident_installs=rr.resident_installs,
           switches_resident=rr.switches, switches_drain=rd.switches)
    metric("kernel/mixed_task_step_ratio", step_ratio, "x_vs_drain",
           guard=("higher", 0.15))
    return ok


# the continuous-batching smoke matrix: one arch per served family, each
# with its slot-state protocol (dense KV pages, encdec cross-KV admitted
# as position-free rows, vlm image prefix occupying decoder positions,
# SSM/hybrid recurrent rows).  SSM/hybrid prompt lengths are multiples of
# the tiny SSMConfig.chunk (chunked-SSD prefill constraint).
FAMILY_ARCHS = ("llama3.2-1b", "whisper-medium", "llava-next-mistral-7b",
                "xlstm-125m", "zamba2-7b")
_KV_SHAPES = ((6, 4, 0), (5, 9, 0), (7, 3, 1), (6, 6, 2), (4, 12, 3))
_CHUNKED_SHAPES = ((8, 4, 0), (16, 7, 0), (8, 3, 1), (24, 5, 3), (16, 6, 6))


def _family_requests(cfg, rng: np.random.Generator):
    """Mixed-length staggered workload for one family, prefixes included."""
    from repro.train.serve import Request
    shapes = _CHUNKED_SHAPES if cfg.family in ("ssm", "hybrid") \
        else _KV_SHAPES
    reqs = []
    for s, n_new, arrival in shapes:
        prefix = None
        if cfg.family == "encdec":
            prefix = rng.normal(size=(cfg.enc_frames, cfg.d_model)
                                ).astype(np.float32)
        elif cfg.family == "vlm":
            prefix = rng.normal(size=(cfg.n_img_tokens, cfg.d_model)
                                ).astype(np.float32)
        reqs.append(Request(
            tokens=rng.integers(0, cfg.vocab_size, size=s).astype(np.int32),
            n_new=n_new, arrival_step=arrival, prefix=prefix))
    return reqs


def family_serving(report, check: bool = False) -> bool:
    """Continuous batching across every served family vs lockstep.

    One tiny arch per family through the SAME slot pool code path: 5
    mixed-length staggered requests over 2 slots, gated on token-for-token
    equality with per-request lockstep ``generate`` and zero bubble
    slot-steps.  Both counters are deterministic, so the per-family rows
    feed the perf-trajectory gate at band 0.
    """
    from repro.serve import ServeConfig
    from repro.train.serve import Engine

    ok = True
    for arch in FAMILY_ARCHS:
        cfg = configs.make_tiny(configs.get_config(arch)).replace(
            tuning=TuningConfig(mode="peqa"),
            quant=QuantConfig(bits=4, n_grid=2))
        fam = cfg.family
        api = registry.build(cfg)
        rng = jax.random.PRNGKey(0)
        p, _ = policies.prepare(api.init(rng), cfg, rng)
        eng = Engine(api, jax.tree.map(jnp.asarray, p))
        reqs = _family_requests(cfg, np.random.default_rng(11))
        rep = eng.serve(reqs, ServeConfig(n_slots=2))

        equal = True
        for i, r in enumerate(reqs):
            pref = None if r.prefix is None else jnp.asarray(r.prefix)[None]
            ref = np.asarray(eng.generate(jnp.asarray(r.tokens)[None],
                                          n_new=r.n_new, prefix=pref))
            want = list(ref[0, len(r.tokens):])
            if rep.tokens[i] != want:
                report(f"kernel/family_{fam}", 0.0,
                       f"FAIL {arch} req{i}: continuous diverges from "
                       f"lockstep")
                equal = ok = False
                break
        if rep.bubble_slot_steps != 0:
            report(f"kernel/family_{fam}", 0.0,
                   f"FAIL {arch}: {rep.bubble_slot_steps} bubble slot-steps")
            ok = False
        report(f"kernel/family_{fam}", rep.wall_s * 1e6,
               f"{arch}: {len(reqs)} reqs / 2 slots steps={rep.steps} "
               f"bubbles={rep.bubble_slot_steps} "
               f"prefill_compiles={rep.prefill_compiles} "
               f"tokens==lockstep: {equal}")
        metric(f"serving/family_{fam}_token_equality", int(equal), "bool",
               guard=("higher", 0.0), arch=arch, steps=rep.steps,
               prefill_compiles=rep.prefill_compiles)
        metric(f"serving/family_{fam}_bubble_slot_steps",
               rep.bubble_slot_steps, "slot_steps", guard=("lower", 0.0),
               arch=arch)
    return ok


def production_serving(report, check: bool = False,
                       traffic_kind: str = "poisson", seed: int = 0) -> bool:
    """Production traffic through the event-driven admission loop.

    Seeded Poisson (or trace-replay) arrivals over a 3-task bank engine,
    both schedulers, SLO percentiles on the VIRTUAL clock (TTFT/TPOT/
    queue-wait/e2e — deterministic for a seeded workload, so the
    trajectory gate can hold them to a band).  Three gates in check mode:

      * determinism — a second same-seed run must produce the identical
        stable (non-wall) metric rows;
      * overload honesty — a bounded queue over an undersized pool must
        SHED, never stall: every request accounted served/rejected/shed,
        the queue never exceeds its bound;
      * scheduling never changes tokens — every request served under
        overload decodes the exact tokens of the unloaded run.
    """
    from repro.serve import ServeConfig, driver, traffic
    from repro.train.serve import Engine

    cfg = _serving_cfg()
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)

    bank = ScaleBank()
    bank.add("t0", p)
    rngs = np.random.default_rng(7)
    for t in ("t1", "t2"):
        bank.tasks[t] = {k: (v * rngs.uniform(0.8, 1.2, v.shape)
                             ).astype(v.dtype)
                         for k, v in bank.tasks["t0"].items()}
    tasks = ("t0", "t1", "t2")
    mk = lambda: Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)

    reqs, meta = traffic.make(traffic_kind, vocab=cfg.vocab_size, seed=seed,
                              tasks=tasks, rate=2.0, n_requests=12)
    RUN_META.update(meta)
    ok = True

    for sched in ("resident", "drain"):
        config = ServeConfig(n_slots=4, scheduler=sched)
        mk().serve(reqs, config)                          # compile warmup
        rep, summary = driver.run(mk(), reqs, config, sink=SINK)
        slo = summary["slo"]
        report(f"kernel/serving_{sched}", rep.wall_s * 1e6,
               f"{meta['traffic']} seed={seed} served={rep.n_served}/"
               f"{len(reqs)} steps={rep.steps} "
               f"ttft_p50={slo['ttft_s']['p50']:.2f} "
               f"ttft_p99={slo['ttft_s']['p99']:.2f} "
               f"tpot_p50={slo['tpot_s']['p50']:.2f} "
               f"tpot_p99={slo['tpot_s']['p99']:.2f} "
               f"tok/s={summary['tok_s_wall']:.0f}")
        if rep.n_served != len(reqs):
            report(f"kernel/serving_{sched}", 0.0,
                   f"FAIL {len(reqs) - rep.n_served} requests not served "
                   f"under an unloaded pool")
            ok = False

    # ---- determinism: same seed, fresh engine -> identical stable rows
    reqs2, _ = traffic.make(traffic_kind, vocab=cfg.vocab_size, seed=seed,
                            tasks=tasks, rate=2.0, n_requests=12)
    sink2 = telemetry.MetricSink()
    driver.run(mk(), reqs2, ServeConfig(n_slots=4, scheduler="resident"),
               sink=sink2)
    first = [m for m in SINK.metrics
             if m["name"].startswith("serving/resident") and not m.get("wall")]
    second = [m for m in sink2.metrics if not m.get("wall")]
    if first != second:
        diff = [(a, b) for a, b in zip(first, second) if a != b]
        report("kernel/serving_determinism", 0.0,
               f"FAIL same-seed rerun diverged: {diff[:3]}")
        ok = False
    metric("serving/determinism", int(first == second), "bool",
           guard=("higher", 0.0))
    report("kernel/serving_determinism", 0.0,
           f"same-seed rerun stable rows identical: {first == second}")

    # ---- overload: undersized pool + bounded queue must shed, not stall
    config_o = ServeConfig(n_slots=2, scheduler="auto", queue_bound=2,
                           shed_after_s=6.0)
    rep_o, _ = driver.run(mk(), reqs, config_o, sink=SINK,
                          label="serving_overload")
    rep_u = mk().serve(reqs, ServeConfig(n_slots=2, scheduler="auto"))
    accounted = rep_o.n_served + rep_o.n_rejected + rep_o.n_shed
    if accounted != len(reqs):
        report("kernel/serving_overload", 0.0,
               f"FAIL {len(reqs) - accounted} requests unaccounted")
        ok = False
    if rep_o.peak_queue_depth > config_o.queue_bound:
        report("kernel/serving_overload", 0.0,
               f"FAIL queue grew to {rep_o.peak_queue_depth} > bound "
               f"{config_o.queue_bound}")
        ok = False
    if check and rep_o.n_served >= len(reqs):
        report("kernel/serving_overload", 0.0,
               "FAIL overload arm shed nothing (not an overload?)")
        ok = False
    for i, m in enumerate(rep_o.requests):
        if m.status == "served" and m.tokens != rep_u.requests[i].tokens:
            report("kernel/serving_overload", 0.0,
                   f"FAIL req{i} tokens diverge under load")
            ok = False
            break
    metric("serving/overload_accounted", int(accounted == len(reqs)),
           "bool", guard=("higher", 0.0), n_served=rep_o.n_served,
           n_rejected=rep_o.n_rejected, n_shed=rep_o.n_shed,
           peak_queue_depth=rep_o.peak_queue_depth)
    report("kernel/serving_overload", 0.0,
           f"served={rep_o.n_served} rejected={rep_o.n_rejected} "
           f"shed={rep_o.n_shed} peak_queue={rep_o.peak_queue_depth} "
           f"(bound {config_o.queue_bound}) tokens==unloaded_run")
    return ok


def scalebank_tiering(report, check: bool = False, n_tasks: int = 10_000,
                      seed: int = 0) -> bool:
    """Million-task-shaped ScaleBank: 10k on-disk tasks, zipfian traffic.

    Writes ``n_tasks`` npz task files (one canonical blob copied, with
    DISTINCT scales for every task the seeded zipfian stream actually
    touches), opens the bank lazily — the init gate is ZERO payload bytes
    deserialized — and serves the stream through the resident scheduler
    with nonzero virtual tier costs, so the admission-loop prefetcher has
    something to hide.  Deterministic gates (check mode):

      * init touches zero task payload bytes (the lazy-index contract);
      * token-for-token equality with the same bank eagerly warmed
        (``warm_all`` — the pre-tiering init behavior);
      * resident-hit swap p99 / ``step_s`` < 1 on the virtual clock
        (a device-tier admit must never stall a decode step);
      * most admits land device/host (the prefetcher is actually hiding
        the zipf tail's disk loads).

    The budgets are fixed (no EOS), so scheduling — and with it every
    tier classification — depends only on arrivals and budgets, never on
    sampled token values: the rows are deterministic and guarded.
    """
    import io
    import os
    import shutil
    import tempfile

    from repro.serve import ServeConfig
    from repro.train.serve import Engine, Request

    cfg = configs.paper_lm(n_layers=1, d_model=32, n_heads=2, d_ff=64,
                           vocab=64).replace(
        tuning=TuningConfig(mode="peqa"), quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)
    base_scales = sb.extract_scales(p)

    rngs = np.random.default_rng(seed + 13)
    n_requests = 48
    task_ids = (rngs.zipf(1.5, size=n_requests) - 1) % n_tasks
    tname = lambda i: f"task{i:05d}"

    root = tempfile.mkdtemp(prefix="scalebank_tiering_")
    ok = True
    try:
        buf = io.BytesIO()
        np.savez(buf, **base_scales)
        blob = buf.getvalue()
        t0 = time.perf_counter()
        for i in range(n_tasks):
            with open(os.path.join(root, f"{tname(i)}.npz"), "wb") as f:
                f.write(blob)
        for i in sorted(set(task_ids)):     # touched tasks get real content
            bumped = {k: (v * rngs.uniform(0.8, 1.2, v.shape)
                          ).astype(v.dtype) for k, v in base_scales.items()}
            with open(os.path.join(root, f"{tname(i)}.npz"), "wb") as f:
                np.savez(f, **bumped)
        t_write = time.perf_counter() - t0

        t0 = time.perf_counter()
        bank = ScaleBank(root, host_capacity=16)
        t_open = (time.perf_counter() - t0) * 1e6
        init_bytes = bank.stats.payload_bytes_loaded
        if len(bank.tasks) != n_tasks or init_bytes != 0:
            report("serving/tiering_init", 0.0,
                   f"FAIL lazy open: {len(bank.tasks)} tasks indexed, "
                   f"{init_bytes} payload bytes loaded (want {n_tasks}, 0)")
            ok = False

        reqs = [Request(
            tokens=(np.arange(6, dtype=np.int32) * (i + 1)) % cfg.vocab_size,
            n_new=(4, 6, 8)[i % 3], task=tname(task_ids[i]),
            arrival_s=round(i * 0.7, 6)) for i in range(n_requests)]
        config = ServeConfig(n_slots=4, scheduler="resident",
                             resident_tasks=4, prefetch_depth=4,
                             disk_load_s=0.4, install_s=0.1)
        eng = Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)
        eng.serve(reqs, config)                           # compile warmup
        eng = Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)
        rep = eng.serve(reqs, config)

        # eager reference: same directory warmed up front (the pre-tiering
        # behavior) — tokens must match bit-for-bit
        eager_bank = ScaleBank(root)
        t0 = time.perf_counter()
        eager_bank.warm_all()
        t_warm = time.perf_counter() - t0
        ref = Engine(api, jax.tree.map(jnp.asarray, p),
                     bank=eager_bank).serve(reqs, config)
        tokens_equal = rep.tokens == ref.tokens
        if not tokens_equal:
            report("serving/tiering", 0.0,
                   "FAIL tiered tokens diverge from eager bank")
            ok = False

        n_adm = rep.tier_device_hits + rep.tier_host_hits \
            + rep.tier_disk_loads
        device_rate = rep.tier_device_hits / max(n_adm, 1)
        warm_rate = (rep.tier_device_hits + rep.tier_host_hits) \
            / max(n_adm, 1)
        p99_dev = rep.swap_percentiles("device")["p99"]
        p99_ratio = p99_dev / config.step_s
        if p99_ratio >= 1.0:
            report("serving/tiering", 0.0,
                   f"FAIL resident-hit swap p99 {p99_dev:.3f}s >= one "
                   f"decode step ({config.step_s}s)")
            ok = False
        if check and warm_rate < 0.5:
            report("serving/tiering", 0.0,
                   f"FAIL prefetcher hid too little: only "
                   f"{warm_rate:.0%} of admits device/host")
            ok = False

        report("serving/tiering", t_open,
               f"{n_tasks} tasks open={t_open:.0f}us (write={t_write:.1f}s "
               f"warm_all={t_warm:.1f}s) init_payload={init_bytes}B "
               f"admits: device={rep.tier_device_hits} "
               f"host={rep.tier_host_hits} disk={rep.tier_disk_loads} "
               f"hidden={rep.prefetch_hidden_s:g}s "
               f"swap_p99_device={p99_dev:g}s "
               f"bank_loads={rep.bank_disk_loads} "
               f"evictions={rep.bank_host_evictions} "
               f"tokens==eager: {tokens_equal}")
        metric("serving/tiering_open", t_open, "us", wall=True,
               n_tasks=n_tasks, warm_all_s=t_warm)
        metric("serving/tiering_init_payload_bytes", init_bytes, "B",
               guard=("lower", 0.0))
        metric("serving/tiering_token_equal", int(tokens_equal), "bool",
               guard=("higher", 0.0))
        metric("serving/tiering_resident_swap_p99_ratio",
               round(p99_ratio, 9), "x_step", guard=("lower", 0.0))
        metric("serving/tiering_device_rate", round(device_rate, 6),
               "frac", guard=("higher", 0.15),
               host_hits=rep.tier_host_hits,
               disk_loads=rep.tier_disk_loads,
               prefetch_issued=rep.prefetch_issued)
        metric("serving/tiering_warm_rate", round(warm_rate, 6), "frac",
               guard=("higher", 0.15))
        metric("serving/tiering_hidden_s", round(rep.prefetch_hidden_s, 9),
               "s", guard=("higher", 0.15),
               swap_wait_total_s=round(rep.swap_wait_total_s, 9))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return ok


def run(report, traffic_kind: str = "poisson", seed: int = 0):
    traffic_model(report)
    gemv_roofline(report)
    xla_path_walltime(report)
    task_switch(report)
    sharded_serving(report)
    continuous_serving(report)
    mixed_task_serving(report)
    family_serving(report)
    production_serving(report, traffic_kind=traffic_kind, seed=seed)
    scalebank_tiering(report, seed=seed)


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--check-sharded", action="store_true",
                    help="run only the roofline + sharded + continuous + "
                         "mixed-task serving benches; exit 1 "
                         "on sharding problems / swap collectives / vocab "
                         "all-gathers / bubble steps / bytes-per-token "
                         "regression / task-drain idle under the resident "
                         "scheduler / tiered-bank init "
                         "payload bytes != 0 or tiered-vs-eager token "
                         "mismatch (the serve-smoke CI gate)")
    ap.add_argument("--emit-json", metavar="DIR", default=None,
                    help="write BENCH_kernels.json and BENCH_serving.json "
                         "into DIR (CI artifacts)")
    ap.add_argument("--traffic", default="poisson",
                    help="production-serving arrival process "
                         "(poisson | trace)")
    ap.add_argument("--seed", type=int, default=0,
                    help="production-serving traffic seed")
    args = ap.parse_args()

    def _report(n, us, d):
        print(f"{n},{us:.1f},{d}")

    if args.check_sharded:
        passed = gemv_roofline(_report, check=True)
        passed = sharded_serving(_report, check=True) and passed
        passed = continuous_serving(_report, check=True) and passed
        passed = mixed_task_serving(_report, check=True) and passed
        passed = family_serving(_report, check=True) and passed
        passed = production_serving(_report, check=True,
                                    traffic_kind=args.traffic,
                                    seed=args.seed) and passed
        passed = scalebank_tiering(_report, check=True,
                                   seed=args.seed) and passed
        if args.emit_json:
            emit_json(args.emit_json)
        print(f"[check-sharded] {'OK' if passed else 'FAILED'}")
        sys.exit(0 if passed else 1)
    run(_report, traffic_kind=args.traffic, seed=args.seed)
    if args.emit_json:
        emit_json(args.emit_json)
