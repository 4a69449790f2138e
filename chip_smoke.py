#!/usr/bin/env python3
"""Bring-up smoke test: PEQA tuning and multi-task serving on one TPU chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded serving + (2, 2) train step

The model is ``llama3.2-1b`` at its published widths (16 layers, d_model
2048, d_ff 8192, vocab 128256) with random weights from a seed, 4-bit
per-channel PEQA in the nibble layout.  Everything runs in this one process
through the repo's own entry points:

  1. tune  — a few scales-only steps of ``train.step.build_train_step``
             driven by ``train.loop.train`` (the ``launch/train.py`` path);
  2. serve — the tuned scale set and the RTN one in a ``ScaleBank``, a mixed-
             task request stream through ``Engine.serve`` under the resident
             scheduler: prefill on the task-stacked GEMM kernel, decode on
             the task-stacked GEMV kernel.  Every request's tokens must equal
             lockstep ``Engine.generate`` under its task's scales;
  3. hlo   — the compiled mixed-task decode step holds Mosaic kernels;
  4. check — prefill-then-decode logits against a float32 dequantised
             reference forward (``impl="ref"``, highest matmul precision),
             for the float32 Engine and for the served bfloat16 one.

``--four-chips`` runs only the paths that exist across chips: the same model
served through the sharded ``Engine`` on a (1, 4) mesh against the
single-device ``Engine``, and two PEQA train steps on a (2, 2) mesh against
the single-device steps.

Every failure exits non-zero; there is no fallback off the chip.  Timings
printed here are bring-up timings, not benchmark numbers.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import (OptimConfig, QuantConfig, TrainConfig,  # noqa: E402
                                TuningConfig)
from repro.core import policies  # noqa: E402
from repro.core.scale_bank import ScaleBank  # noqa: E402
from repro.data import pipeline, synthetic  # noqa: E402
from repro.dist import backend  # noqa: E402
from repro.dist import context as dctx  # noqa: E402
from repro.dist import sharding as shard_rules  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import registry, transformer  # noqa: E402
from repro.optim.adamw import make_optimizer  # noqa: E402
from repro.serve import ServeConfig  # noqa: E402
from repro.train import loop, step  # noqa: E402
from repro.train.serve import Engine, Request  # noqa: E402
from repro.train.state import shard_state  # noqa: E402

ARCH = "llama3.2-1b"
TUNE_STEPS, TUNE_BATCH, TUNE_SEQ = 4, 8, 512
N_SLOTS, CACHE_LEN = 4, 96
# (prompt tokens, new tokens, task, arrival step): prompts bucket to 64 rows
# (> ops.GEMV_MAX_M: the GEMM kernel) or 16 rows (the GEMV kernel)
REQUESTS = ((40, 8, "tuned", 0), (12, 12, "rtn", 0), (48, 6, "rtn", 1),
            (10, 10, "tuned", 2), (56, 8, "tuned", 3), (14, 12, "rtn", 4))
# logits check: 2 × 40 prompt rows take the GEMM kernel, decode the GEMV
CHECK_BATCH, CHECK_PROMPT, CHECK_NEW = 2, 40, 6
# max|Δlogit| / max|logit| allowed against the float32 reference.  Both
# sides compute in f32 (the kernel contracts exact integer codes with f32
# activations at HIGHEST precision), so they differ only in the order of
# their f32 sums: ~1e-6 relative per layer.  Rounding the kernels'
# activations to bf16 costs ~2^-9 relative per product — the run measures
# that control (bf16 operands in the otherwise float32 model) and requires
# it to fail this bound.
LOGITS_TOL = 1e-4
# the same for the served bfloat16 Engine, whose residual stream, attention
# and kernel operands are all bf16: a whole bf16 forward of this model sat at
# 2.9e-2 on the chip, while a wrong scale, group or cast moves logits O(1)
BF16_LOGITS_TOL = 1e-1
# |Δ| / |value| allowed between a mesh train step and the one-device step
# (loss, gradient norm, and the loss after the update): both run the same
# bf16 model; only the order of the sharded reductions differs.
LOSS_TOL = 1e-3
# what a Pallas kernel compiles to on the TPU
KERNEL_MARK = "tpu_custom_call"


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"[chip_smoke] FAIL: {msg}")


def smoke_config():
    return configs.get_config(ARCH).replace(
        tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, group_size=None, layout="nibble"))


def build(cfg):
    """(api, PEQA backbone, trainable mask) from random weights."""
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    # one program: the fp32 init never has to live beside its eager copies
    params = jax.jit(lambda r: policies.transform(api.init(r), cfg, r))(rng)
    return api, params, policies.make_mask(params, cfg)


def tune(api, cfg, params, mask, *, steps: int, batch: int, seq: int):
    """PEQA scales-only steps; returns (tuned params, losses, step seconds)."""
    tcfg = TrainConfig(steps=steps, batch_size=batch, seq_len=seq,
                       log_every=1, ckpt_every=10 ** 9,
                       watchdog_timeout_s=3600.0,
                       optim=OptimConfig(lr=2e-3, warmup_steps=1))
    toks = synthetic.corpus(cfg.vocab_size, 2 * steps * batch * seq + seq + 1)
    data = pipeline.PackedLM(toks, batch, seq)
    opt = make_optimizer(tcfg.optim, tcfg.steps)
    p = jax.tree.map(jnp.array, params)            # the step donates its state
    state = {"params": p, "opt": opt.init(p, mask), "step": jnp.int32(0)}
    train_step = step.build_train_step(api, cfg, tcfg, mask, opt)
    seconds = []

    def timed(state, b):
        t0 = time.perf_counter()
        out = jax.block_until_ready(train_step(state, b))
        seconds.append(time.perf_counter() - t0)
        return out

    state, hist = loop.train(state, timed, data, tcfg, log=say)
    return state["params"], [h["loss"] for h in hist], seconds


def mixed_requests(vocab: int):
    rng = np.random.default_rng(0)
    return [Request(tokens=rng.integers(0, vocab, s).astype(np.int32),
                    n_new=n, task=task, arrival_step=at)
            for s, n, task, at in REQUESTS]


def check_lockstep(engine, reqs, served, cache_len: int):
    """Each served request's tokens against lockstep ``Engine.generate``
    under its task's scales — the scheduler's token-for-token contract,
    with the plain kernels on one side and the task-stacked ones on the
    other."""
    bad = []
    for task in sorted({r.task for r in reqs}):
        engine.switch_task(task)
        for i, r in enumerate(reqs):
            if r.task != task:
                continue
            out = engine.generate(jnp.asarray(r.tokens)[None], r.n_new,
                                  cache_len=cache_len)
            want = [int(t) for t in np.asarray(out[0, r.n_prompt:])]
            if want != served[i]:
                bad.append(f"request {i} ({task}): served {served[i]}, "
                           f"lockstep {want}")
    if bad:
        fail("served tokens differ from lockstep generate: "
             + "; ".join(bad))


def serve(engine, reqs, n_slots: int, cache_len: int):
    """Serve ``reqs`` under the resident scheduler; fail unless every request
    is served with all its tokens."""
    rep = engine.serve(reqs, ServeConfig(n_slots=n_slots, cache_len=cache_len,
                                         scheduler="resident"))
    for i, (r, m) in enumerate(zip(reqs, rep.requests)):
        got = m.n_generated
        say(f"request {i}: task={r.task} prompt={r.n_prompt} "
            f"n_new={r.n_new} {m.status} tokens={got}")
        if m.status != "served" or got != r.n_new:
            fail(f"request {i} ended {m.status} with {got}/{r.n_new} tokens")
    if rep.scheduler != "resident" or rep.task_drain_idle_slot_steps:
        fail(f"scheduler {rep.scheduler} idled "
             f"{rep.task_drain_idle_slot_steps} slot-steps on task drain")
    return rep


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def reference_logits(cfg, params, toks, prompt_len: int, impl: str = "ref"):
    """Dequantised forward (``impl="ref"``) over the generated sequence,
    teacher forced and without a KV cache: the logits each new token was
    sampled from."""
    fwd = jax.jit(lambda p, t: transformer.forward(p, t, cfg)[0])
    with ops.force_impl(impl):
        return fwd(params, toks[:, :-1])[:, prompt_len - 1:]


@contextlib.contextmanager
def bf16_kernel_operands():
    """Within: every quantized linear rounds its activations to bf16 and
    takes the kernels' bf16 branch; the rest of the model is unchanged."""
    plain = ops.quant_matmul

    def rounded(x, *args, **kw):
        return plain(x.astype(jnp.bfloat16), *args, **kw).astype(x.dtype)
    ops.quant_matmul = rounded
    try:
        yield
    finally:
        ops.quant_matmul = plain


def check_logits(cfg, params, *, batch: int, prompt_len: int, n_new: int):
    """Errors against the float32 reference at highest matmul precision:
    the float32 Engine's prefill-then-decode logits, the control (the
    reference's float32 model with bf16-operand kernels), and the served
    bfloat16 Engine's logits (reference teacher forced on its tokens)."""
    cfg32 = cfg.replace(dtype="float32")
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        toks, got = Engine(registry.build(cfg32), params).generate(
            prompt, n_new, return_logits=True)
        want = reference_logits(cfg32, params, toks, prompt_len)
        with bf16_kernel_operands():
            control = reference_logits(cfg32, params, toks, prompt_len,
                                       impl=ops.default_impl())
        toks16, got16 = Engine(registry.build(cfg), params).generate(
            prompt, n_new, return_logits=True)
        want16 = reference_logits(cfg32, params, toks16, prompt_len)
    return (rel_err(got, want), rel_err(control, want),
            rel_err(got16, want16))


def device_line() -> str:
    d = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}})


def one_chip(cfg) -> None:
    t0 = time.perf_counter()
    api, backbone, mask = build(cfg)
    jax.block_until_ready(backbone)
    n_train = policies.trainable_count(backbone, mask)
    n_total = sum(int(l.size) for l in jax.tree.leaves(backbone))
    say(f"built {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}) in "
        f"{time.perf_counter() - t0:.1f}s; trainable {n_train:,} of "
        f"{n_total:,} stored values ({100 * n_train / n_total:.4f}%)")

    # ---- 1. PEQA tuning
    tuned, losses, secs = tune(api, cfg, backbone, mask, steps=TUNE_STEPS,
                               batch=TUNE_BATCH, seq=TUNE_SEQ)
    say(f"tune losses: {' '.join(f'{l:.4f}' for l in losses)}")
    if len(losses) != TUNE_STEPS or not np.all(np.isfinite(losses)):
        fail(f"tuning losses not finite: {losses}")
    warm = float(np.median(secs[1:]))
    say(f"bring-up timing, not a benchmark: first train step {secs[0]:.2f}s "
        f"(compile included), warm step {warm:.3f}s "
        f"({TUNE_BATCH}x{TUNE_SEQ} tokens)")

    # ---- 2. multi-task serving
    bank = ScaleBank()
    bank.add("tuned", tuned)
    bank.add("rtn", backbone)
    del tuned
    engine = Engine(api, backbone, bank=bank)
    reqs = mixed_requests(cfg.vocab_size)
    t0 = time.perf_counter()
    rep = serve(engine, reqs, N_SLOTS, CACHE_LEN)
    t1 = time.perf_counter()
    again = serve(engine, reqs, N_SLOTS, CACHE_LEN)
    t2 = time.perf_counter()
    if again.tokens != rep.tokens:
        fail("a second serve of the same stream gave other tokens")
    say(f"served {rep.n_served}/{len(reqs)} requests, {rep.decoded} tokens "
        f"in {rep.steps} decode steps, {N_SLOTS} slots, "
        f"{rep.prefill_compiles} prefill shapes")
    say(f"bring-up timing, not a benchmark: serve {t1 - t0:.2f}s "
        f"(compiles included), again warm {t2 - t1:.3f}s")
    check_lockstep(engine, reqs, rep.tokens, CACHE_LEN)
    say(f"served tokens equal lockstep generate under each task's scales "
        f"({len(reqs)}/{len(reqs)} requests)")

    # ---- 3. the served decode step runs Mosaic kernels
    t0 = time.perf_counter()
    n_kernels = engine.slotted_decode_hlo(N_SLOTS, CACHE_LEN).count(
        KERNEL_MARK)
    say(f"mixed-task decode step: {n_kernels} {KERNEL_MARK} "
        f"({time.perf_counter() - t0:.1f}s to lower and compile)")
    if n_kernels == 0:
        fail("the compiled decode step holds no Pallas kernel")

    # ---- 4. logits against the float32 reference
    params = bank.switch(backbone, "tuned")
    del engine, backbone
    err, control, err16 = check_logits(cfg, params, batch=CHECK_BATCH,
                                       prompt_len=CHECK_PROMPT,
                                       n_new=CHECK_NEW)
    say(f"logits vs float32 reference: max rel err {err:.3e} "
        f"(tolerance {LOGITS_TOL:.0e}); control with bf16 kernel operands "
        f"{control:.3e}; served bf16 Engine {err16:.3e} (tolerance "
        f"{BF16_LOGITS_TOL:.0e})")
    if not err <= LOGITS_TOL:
        fail(f"logits differ from the reference by {err:.3e}")
    if not control > LOGITS_TOL:
        fail(f"the tolerance does not separate bf16 kernel operands "
             f"({control:.3e} <= {LOGITS_TOL:.0e})")
    if not err16 <= BF16_LOGITS_TOL:
        fail(f"bf16 Engine logits differ from the reference by {err16:.3e}")


def four_chips(cfg) -> None:
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    api, backbone, mask = build(cfg)
    host = jax.tree.map(np.asarray, backbone)

    # ---- sharded serving against one device
    cfg32 = cfg.replace(dtype="float32")
    api32 = registry.build(cfg32)
    ctx = dctx.make_ctx(make_mesh((1, 4), ("data", "model")))
    problems = shard_rules.validate_for_mesh(host, ctx.mesh)
    if problems:
        fail(f"sharding problems on the (1, 4) mesh: {problems[:5]}")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (CHECK_BATCH, CHECK_PROMPT))
    prompt = jnp.asarray(prompt, jnp.int32)
    with jax.default_matmul_precision("highest"):
        single = Engine(api32, jax.device_put(host, devs[0]))
        t1, l1 = single.generate(prompt, CHECK_NEW, return_logits=True)
        mesh_eng = Engine(api32, jax.device_put(
            host, shard_rules.named_shardings(ctx, host)),
            ctx=ctx, logitshard=True)
        t4, l4 = mesh_eng.generate(jax.device_put(prompt, ctx.sharding()),
                                   CHECK_NEW, return_logits=True)
    err = rel_err(l4, l1)
    same = bool(np.array_equal(np.asarray(t1), np.asarray(t4)))
    say(f"(1, 4) mesh Engine vs one device: tokens equal {same}, "
        f"logits max rel err {err:.3e} (tolerance {LOGITS_TOL:.0e})")
    if not same or not err <= LOGITS_TOL:
        fail("sharded serving differs from the single-device Engine")
    del single, mesh_eng

    # ---- two PEQA train steps on a (2, 2) mesh against one device
    tcfg = TrainConfig(steps=2, batch_size=TUNE_BATCH, seq_len=TUNE_SEQ,
                       log_every=1, ckpt_every=10 ** 9, watchdog_timeout_s=3600.0,
                       optim=OptimConfig(lr=2e-3, warmup_steps=1))
    toks = synthetic.corpus(cfg.vocab_size, 4 * TUNE_BATCH * TUNE_SEQ)
    data = pipeline.PackedLM(toks, TUNE_BATCH, TUNE_SEQ)
    opt = make_optimizer(tcfg.optim, tcfg.steps)

    def fresh():
        p = jax.tree.map(jnp.array, backbone)
        return {"params": p, "opt": opt.init(p, mask), "step": jnp.int32(0)}

    _, one = loop.train(fresh(), step.build_train_step(
        api, cfg, tcfg, mask, opt), data, tcfg, log=say)
    mesh = make_mesh((2, 2), ("data", "model"))
    state = shard_state(fresh(), mesh)
    with dctx.use_mesh(dctx.make_ctx(mesh)):
        ts = step.build_train_step(api, cfg, tcfg, mask, opt, mesh=mesh,
                                   state_example=state,
                                   batch_example=data.batch_at(0))
        _, four = loop.train(state, ts, data, tcfg, log=say)
    # step 1's loss precedes any gradient; its gradient norm checks the
    # data-axis reduction, and step 2's loss the update that used it
    pairs = (("step-1 loss", one[0]["loss"], four[0]["loss"]),
             ("step-1 grad norm", one[0]["grad_norm"], four[0]["grad_norm"]),
             ("step-2 loss", one[1]["loss"], four[1]["loss"]))
    for what, a, b in pairs:
        d = abs(b - a) / abs(a)
        say(f"(2, 2) mesh train {what} {b:.6f} vs one device {a:.6f}: "
            f"rel diff {d:.2e} (tolerance {LOSS_TOL:.0e})")
        if not np.isfinite(b) or not d <= LOSS_TOL:
            fail(f"the mesh train {what} differs from the one-device run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving and (2, 2) mesh train "
                         "step checks, on a four-chip host")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        fail(f"needs a TPU; JAX found platform {platform!r}")
    impl = ops.default_impl()
    if impl != "pallas":
        fail(f"quantized linears would run impl {impl!r}, not the Pallas "
             f"kernels (REPRO_QMM_IMPL or force_impl)")
    cache = Path(backend.enable_compile_cache())
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    say(f"compile cache: {cache} ({n_cached} entries at start)")
    cfg = smoke_config()
    if args.four_chips:
        four_chips(cfg)
    else:
        one_chip(cfg)
    print(device_line(), flush=True)


if __name__ == "__main__":
    main()
