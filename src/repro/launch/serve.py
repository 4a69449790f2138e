"""Serving launcher: one PEQA backbone, many tasks, batched greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --tiny \
        --bits 4 --tasks taskA,taskB --n-new 24

Tunes a small scale-set per task on distinct synthetic corpora (stand-ins
for per-task adapters shipped to the fleet), then serves round-robin across
tasks with O(MB) scale hot-swaps (paper Table 1's PEQA row).

Mesh mode (``--mesh D,M``) is the dist subsystem's serving hot path: the
backbone is homed on a (data, model) mesh per ``dist.sharding``, task swaps
move per-shard local bytes only, and ``--logitshard`` (default on) keeps
decode logits vocab-sharded with the shard-local sampler — no vocab
all-gather in the loop.  On a CPU-only box, fake the devices first:

    REPRO_FAKE_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
        --tiny --mesh 2,4

``--continuous`` switches the traffic loop to the continuously-batched
engine: an arrival-simulating driver builds a mixed-length, mixed-task
request stream (staggered arrivals on the decode-step clock) and pushes it
through ``Engine.serve`` — paged KV slots, mid-loop admit/evict, per-slot
positions.  ``--scheduler`` picks the mixed-task policy: ``resident``
(stacked per-task scales stay device-resident and decode gathers each
slot's row in-kernel, no drain-before-switch), ``drain`` (the pool drains
before each scale swap) or ``auto`` (the default: ``resident`` where the
workload allows it, else ``drain``).  It exits
non-zero if any request is dropped, any bubble step is observed (a
finished sequence occupying a decode step), or the resident scheduler
idles a single slot-step on task drain, so CI can run it as a smoke gate:

    REPRO_FAKE_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
        --tiny --mesh 2,4 --continuous
"""
from __future__ import annotations

import os

from repro.dist import backend

if os.environ.get("REPRO_FAKE_DEVICES"):
    backend.fake_host_devices(os.environ["REPRO_FAKE_DEVICES"])

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import OptimConfig, QuantConfig, TrainConfig, TuningConfig
from repro.core import policies
from repro.core.scale_bank import ScaleBank
from repro.data import pipeline, synthetic
from repro.dist import context as dctx
from repro.dist import sharding as shard_rules
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.optim.adamw import make_optimizer
from repro.serve import ServeConfig, driver, traffic
from repro.train import loop, step
from repro.train.serve import Engine, Request


def place_prompt(prompt, ctx):
    """Home the prompt BATCH-SHARDED when the batch divides the data axes.

    A fully replicated put (``ctx.sharding()``) makes prefill pay a batch
    reshard on entry — a collective the decode-loop benchmarks never see
    because it happens before the guarded HLO.  Batch-sharded placement is
    prefill's natural input layout (``constrain_tokens``), so the put IS
    the final layout.
    """
    if ctx is None:
        return prompt
    return jax.device_put(
        prompt, ctx.sharding(ctx.batch_axes(prompt.shape[0]), None))


def mixed_workload(tasks, batch, n_new, n_requests, vocab, stagger=2):
    """Arrival-simulating request stream: mixed lengths (n_new/2, n_new,
    2*n_new cycling), mixed tasks (round-robin per arrival wave), prompts
    of 8 tokens, arrivals staggered ``stagger`` decode steps apart."""
    lengths = [max(2, n_new // 2), n_new, 2 * n_new]
    reqs = []
    for i in range(n_requests):
        prompt = (np.arange(8, dtype=np.int32) * (i + 1)) % vocab
        reqs.append(Request(
            tokens=prompt, n_new=lengths[i % len(lengths)],
            task=tasks[(i // batch) % len(tasks)],
            arrival_step=(i // batch) * stagger))
    return reqs


def family_workload(cfg, seed: int = 11):
    """Mixed-length staggered stream for ONE family, prefix state included.

    SSM/hybrid prompt lengths are multiples of the tiny ``SSMConfig.chunk``
    (the chunked-SSD prefill asserts divisibility); encdec requests carry
    synthesized encoder frames and vlm requests image embeddings — the
    per-request prefix state the slot protocol admits once per slot.
    """
    rng = np.random.default_rng(seed)
    shapes = ((8, 4, 0), (16, 7, 0), (8, 3, 1), (24, 5, 3), (16, 6, 6)) \
        if cfg.family in ("ssm", "hybrid") else \
        ((6, 4, 0), (5, 9, 0), (7, 3, 1), (6, 6, 2), (4, 12, 3))
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


def run_family_smoke(engine, cfg, args) -> bool:
    """Untasked continuous serving for ANY registered family, gated on
    token-for-token equality with per-request lockstep ``generate``.

    No tuning, no scale bank — the smoke isolates the slot-state protocol
    (paged KV, position-free recurrent rows, prefix admission) from the
    PEQA task machinery, so every family the registry caps as servable can
    run it, and CI fails on any drift or bubble slot-step.
    """
    reqs = family_workload(cfg, seed=args.seed + 11)
    rep = engine.serve(reqs, ServeConfig(n_slots=2))
    ok = rep.bubble_slot_steps == 0
    if not ok:
        print(f"[serve] FAIL: {rep.bubble_slot_steps} bubble slot-steps")
    for i, r in enumerate(reqs):
        pref = None if r.prefix is None else jnp.asarray(r.prefix)[None]
        ref = np.asarray(engine.generate(jnp.asarray(r.tokens)[None],
                                         n_new=r.n_new, prefix=pref))
        want = list(ref[0, len(r.tokens):])
        match = rep.tokens[i] == want
        print(f"[serve] req{i:02d} n_prompt={r.n_prompt} n_new={r.n_new} "
              f"prefix={'-' if r.prefix is None else r.prefix.shape} "
              f"tokens==lockstep: {match}")
        if not match:
            ok = False
    print(f"[serve] family-smoke {cfg.family} ({cfg.name}): "
          f"steps={rep.steps} bubbles={rep.bubble_slot_steps} "
          f"prefill_compiles={rep.prefill_compiles} "
          f"{'OK' if ok else 'FAILED'}")
    return ok


def run_continuous(engine, cfg, args, tasks):
    if args.traffic == "steps":
        reqs = mixed_workload(tasks, args.batch, args.n_new,
                              n_requests=3 * args.batch,
                              vocab=cfg.vocab_size)
    else:
        reqs, meta = traffic.make(
            args.traffic, vocab=cfg.vocab_size, seed=args.seed,
            tasks=tuple(tasks), rate=args.rate,
            n_requests=3 * args.batch, trace_path=args.trace or None,
            n_new=(max(2, args.n_new // 2), args.n_new, 2 * args.n_new))
        print(f"[serve] traffic: {meta}")
    config = ServeConfig(n_slots=args.batch, scheduler=args.scheduler,
                         prefetch_depth=args.prefetch_depth,
                         host_cache_tasks=args.host_cache or None,
                         disk_load_s=args.disk_load_s,
                         install_s=args.install_s)
    rep, summary = driver.run(engine, reqs, config)
    dropped = [i for i, t in enumerate(rep.tokens) if t is None]
    for i, (r, m) in enumerate(zip(reqs, rep.requests)):
        out = m.tokens
        got = len(out) if out is not None else 0
        print(f"[serve] req{i:02d} task={r.task} n_new={r.n_new} "
              f"arrival={m.arrival_s:g}s {m.status} got={got} "
              f"ttft={m.ttft_s:g} "
              f"sample={out[:4] if out else []}")
    print(f"[serve] continuous[{rep.scheduler}]: {rep.decoded} tokens in "
          f"{rep.steps} steps ({args.batch} slots) "
          f"tok/s={summary['tok_s_wall']:.0f} "
          f"bubble_slot_steps={rep.bubble_slot_steps} "
          f"idle_slot_steps={rep.idle_slot_steps} "
          f"task_drain_idle_slot_steps={rep.task_drain_idle_slot_steps} "
          f"switches={rep.switches} installs={rep.resident_installs}")
    slo = summary["slo"]
    print("[serve] slo: " + " ".join(
        f"{k}_p50={slo[k]['p50']:g} {k}_p99={slo[k]['p99']:g}"
        for k in ("ttft_s", "tpot_s", "e2e_s")))
    if rep.tier_device_hits + rep.tier_host_hits + rep.tier_disk_loads:
        print(f"[serve] tiers: device={rep.tier_device_hits} "
              f"host={rep.tier_host_hits} disk={rep.tier_disk_loads} "
              f"prefetch_issued={rep.prefetch_issued} "
              f"hidden={rep.prefetch_hidden_s:g}s "
              f"swap_wait_total={rep.swap_wait_total_s:g}s "
              f"bank_loads={rep.bank_disk_loads} "
              f"bank_evictions={rep.bank_host_evictions}")
    ok = not dropped and rep.bubble_slot_steps == 0 and all(
        out is not None and len(out) == r.n_new
        for r, out in zip(reqs, rep.tokens))
    if rep.scheduler == "resident" and rep.task_drain_idle_slot_steps != 0:
        print(f"[serve] FAIL: resident scheduler idled "
              f"{rep.task_drain_idle_slot_steps} slot-steps on task drain")
        ok = False
    print(f"[serve] continuous {'OK' if ok else 'FAILED'}")
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU-servable)")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--layout", default="nibble", choices=("nibble", "plane"),
                    help="code packing: 'nibble' is 8 codes/uint32; 'plane' "
                         "stores b bit-planes (any bit width packs densely)")
    ap.add_argument("--tasks", default="taskA,taskB")
    ap.add_argument("--tune-steps", type=int, default=100)
    ap.add_argument("--n-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="'D,M' data×model mesh; serve sharded")
    ap.add_argument("--no-logitshard", action="store_true",
                    help="mesh mode: replicate logits + host argmax instead "
                         "of the shard-local sampler")
    ap.add_argument("--continuous", action="store_true",
                    help="serve an arrival-simulating mixed-length, "
                         "mixed-task stream through the continuously-"
                         "batched engine (paged KV slots, mid-loop "
                         "admit/evict); exits 1 on dropped requests or "
                         "bubble steps (and, under the resident "
                         "scheduler, on ANY task-drain idle slot-step)")
    ap.add_argument("--traffic", default="steps",
                    choices=("steps",) + traffic.KINDS,
                    help="--continuous arrival process: 'steps' is the "
                         "legacy staggered decode-step workload; 'poisson' "
                         "draws seeded wall-clock arrivals at --rate req/s; "
                         "'trace' replays --trace (or a canned burst trace)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="poisson traffic: requests per virtual second")
    ap.add_argument("--seed", type=int, default=0,
                    help="traffic seed (arrivals, prompts, budgets)")
    ap.add_argument("--trace", default="",
                    help="trace traffic: JSON trace file to replay")
    ap.add_argument("--scheduler", default="auto",
                    choices=("auto", "resident", "drain"),
                    help="mixed-task policy for --continuous: 'resident' "
                         "keeps stacked per-task scales device-resident "
                         "and decodes mixed-task slots drain-free via the "
                         "in-kernel row gather; 'drain' waits the pool "
                         "out before each scale swap; 'auto' picks "
                         "resident when supported")
    ap.add_argument("--family-smoke", action="store_true",
                    help="skip tuning and serve an untasked mixed-length "
                         "stream through the continuous engine for THIS "
                         "arch's family (encdec frames / vlm image prefixes "
                         "synthesized, SSM prompts chunk-aligned); exits 1 "
                         "if any request's tokens diverge from lockstep "
                         "generate or any bubble slot-step is observed")
    ap.add_argument("--bank-root", default="",
                    help="persist tuned task scales as npz files here and "
                         "serve through the TIERED bank: the serving bank "
                         "re-opens this directory lazily (filename index "
                         "only) and promotes tasks disk→host→device on "
                         "demand / via the serve loop's prefetcher")
    ap.add_argument("--host-cache", type=int, default=0,
                    help="tiered bank: max deserialized scale sets held in "
                         "the host LRU tier (0 = unbounded)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="continuous serving: distinct upcoming tasks the "
                         "admission loop warms ahead each iteration "
                         "(0 disables prefetch)")
    ap.add_argument("--disk-load-s", type=float, default=0.0,
                    help="virtual seconds one disk→host task load costs")
    ap.add_argument("--install-s", type=float, default=0.0,
                    help="virtual seconds one host→device install costs")
    args = ap.parse_args()
    backend.enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.tiny:
        cfg = configs.make_tiny(cfg)
    cfg = cfg.replace(tuning=TuningConfig(mode="peqa"),
                      quant=QuantConfig(bits=args.bits, n_grid=4,
                                        layout=args.layout),
                      kv_cache_dtype="int8" if args.kv_int8 else "model")
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    backbone, mask = policies.prepare(api.init(rng), cfg, rng)
    if args.family_smoke:
        engine = Engine(api, jax.tree.map(jnp.array, backbone))
        raise SystemExit(0 if run_family_smoke(engine, cfg, args) else 1)
    bank = ScaleBank(root=args.bank_root or None)

    for i, task in enumerate(args.tasks.split(",")):
        toks = synthetic.corpus(cfg.vocab_size, 60_000, seed=17 * (i + 1))
        train_toks, _ = synthetic.split(toks)
        tcfg = TrainConfig(steps=args.tune_steps, batch_size=8, seq_len=64,
                           log_every=10 ** 9, ckpt_every=10 ** 9,
                           optim=OptimConfig(lr=3e-3, warmup_steps=8))
        data = pipeline.PackedLM(train_toks, 8, 64, seed=i)
        opt = make_optimizer(tcfg.optim, tcfg.steps)
        p = jax.tree.map(jnp.array, backbone)
        state = {"params": p, "opt": opt.init(p, mask), "step": jnp.int32(0)}
        ts = step.build_train_step(api, cfg, tcfg, mask, opt)
        state, _ = loop.train(state, ts, data, tcfg, log=lambda m: None)
        bank.add(task, state["params"])
        print(f"[serve] tuned {task}: scale payload "
              f"{bank.nbytes(task):,} B")
    if args.bank_root:
        # serve through the TIERED path: re-open the directory lazily (the
        # index scan touches zero payloads) so disk→host→device promotion
        # and the admission-loop prefetcher actually exercise
        bank = ScaleBank(root=args.bank_root,
                         host_capacity=args.host_cache or None)
        print(f"[serve] tiered bank: {len(bank.tasks)} tasks indexed at "
              f"{args.bank_root!r}, "
              f"{bank.stats.payload_bytes_loaded} payload bytes loaded")

    ctx = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model"))
        ctx = dctx.make_ctx(mesh)
        problems = shard_rules.validate_for_mesh(backbone, mesh)
        if problems:
            raise SystemExit(f"[serve] sharding_problems: {problems[:5]}")
        # snapshot to host first: device_put may alias device-resident
        # buffers, and switch_task donates the engine's tree — the backbone
        # must not share storage with it
        backbone = jax.tree.map(np.asarray, backbone)
        params = jax.device_put(backbone,
                                shard_rules.named_shardings(ctx, backbone))
        print(f"[serve] mesh {shape}: swap moves "
              f"{bank.local_nbytes(args.tasks.split(',')[0], ctx):,} B/device "
              f"of {bank.nbytes(args.tasks.split(',')[0]):,} B total")
    else:
        params = jax.tree.map(jnp.array, backbone)

    engine = Engine(api, params, bank=bank, ctx=ctx,
                    logitshard=ctx is not None and not args.no_logitshard)
    if args.continuous:
        ok = run_continuous(engine, cfg, args, args.tasks.split(","))
        raise SystemExit(0 if ok else 1)
    prompt = place_prompt(jnp.asarray(
        np.tile(np.arange(8, dtype=np.int32), (args.batch, 1))), ctx)
    for task in args.tasks.split(",") * 2:
        dt = engine.switch_task(task)
        t0 = time.perf_counter()
        out = engine.generate(prompt, n_new=args.n_new)
        gen_t = time.perf_counter() - t0
        print(f"[serve] {task}: switch={dt * 1e3:.2f}ms "
              f"gen={gen_t * 1e3:.0f}ms "
              f"tok/s={args.batch * args.n_new / gen_t:.0f} "
              f"sample={np.asarray(out[0, 8:16])}")


if __name__ == "__main__":
    main()
