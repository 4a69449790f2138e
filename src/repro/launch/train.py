"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --tiny \
        --mode peqa --bits 4 --steps 200 --ckpt-dir /tmp/run1

On a real TPU cluster this same entry point runs under multi-host jax
(jax.distributed.initialize() picks up the TPU pod env); the mesh comes from
launch/mesh.py and params/state are sharded by dist/sharding.py rules.  On
CPU it trains the reduced config single-device — same code path, no mesh.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import OptimConfig, QuantConfig, TrainConfig, TuningConfig
from repro.core import policies
from repro.data import pipeline, synthetic
from repro.dist import backend
from repro.dist import context as dctx
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import registry
from repro.optim.adamw import make_optimizer
from repro.train import loop as loop_mod
from repro.train import step as step_mod
from repro.train.state import shard_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--mode", default="peqa",
                    choices=["full", "lora", "lora_optq", "qat", "peqa", "peqa_z"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=None)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", default=None, choices=[None, "int8"])
    ap.add_argument("--mesh", default="none",
                    choices=["none", "debug", "pod", "multipod"])
    args = ap.parse_args()
    backend.enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.tiny:
        cfg = configs.make_tiny(cfg)
    cfg = cfg.replace(
        tuning=TuningConfig(mode=args.mode),
        quant=QuantConfig(bits=args.bits, group_size=args.group_size))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(args.seed)

    print(f"[launch] arch={cfg.name} mode={args.mode} bits={args.bits}")
    params, mask = policies.prepare(api.init(rng), cfg, rng)
    n_train = policies.trainable_count(params, mask)
    n_total = sum(l.size for l in jax.tree.leaves(params))
    print(f"[launch] params={n_total:,} trainable={n_train:,} "
          f"({100 * n_train / n_total:.3f}%)")

    tcfg = TrainConfig(
        steps=args.steps, batch_size=args.batch, seq_len=args.seq,
        optim=OptimConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          grad_compression=args.grad_compression))
    toks = synthetic.corpus(cfg.vocab_size, max(args.steps, 100) * args.batch
                            * args.seq // 4 + 50000, seed=args.seed)
    train_toks, val_toks = synthetic.split(toks)
    data = pipeline.PackedLM(train_toks, args.batch, args.seq, seed=args.seed)

    opt = make_optimizer(tcfg.optim, tcfg.steps)
    state = {"params": params, "opt": opt.init(params, mask),
             "step": jnp.int32(0)}

    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh(2, max(len(jax.devices()) // 2, 1))
    elif args.mesh == "pod":
        mesh = make_production_mesh()
    elif args.mesh == "multipod":
        mesh = make_production_mesh(multi_pod=True)

    if mesh is not None:
        ctx = dctx.make_ctx(mesh)
        state = shard_state(state, mesh)
        batch_ex = data.batch_at(0)
        with dctx.use_mesh(ctx):
            ts = step_mod.build_train_step(
                api, cfg, tcfg, mask, opt, mesh=mesh, state_example=state,
                batch_example=batch_ex)
            state, hist = loop_mod.train(state, ts, data, tcfg,
                                         ckpt_dir=args.ckpt_dir)
    else:
        ts = step_mod.build_train_step(api, cfg, tcfg, mask, opt)
        es = step_mod.build_eval_step(api, cfg)

        def eval_fn(params):
            import numpy as np
            losses = [float(es(params, b)) for b in
                      pipeline.eval_batches(val_toks, args.batch, args.seq)]
            return float(np.mean(losses)) if losses else float("nan")

        state, hist = loop_mod.train(state, ts, data, tcfg,
                                     ckpt_dir=args.ckpt_dir, eval_fn=eval_fn)
    print(f"[launch] done; final loss={hist[-1]['loss']:.4f}")
    return state


if __name__ == "__main__":
    main()
