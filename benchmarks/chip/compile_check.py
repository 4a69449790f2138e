#!/usr/bin/env python3
"""Compile each cell's programs at full size for a described TPU v5e (no
chip needed) and print what the compiler says they hold.

    JAX_PLATFORMS=cpu python benchmarks/chip/compile_check.py [cell ...]

For a serving cell: the slotted decode step over the pool and the slotted
prefill at the largest prompt bucket the mix uses; for a training cell: the
train step.  Each line gives the compiled program's argument, output and
temporary bytes (``memory_analysis``); their sum is that program's peak on
the chip, beside what else the process keeps there.  A program that does
not fit, or a kernel Mosaic refuses, raises here instead of on the chip.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_QMM_IMPL"] = "pallas"     # the kernels, not the CPU path


def main(cells) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import model
    import traffic
    from repro.configs.base import OptimConfig, TrainConfig
    from repro.core import policies
    from repro.core.scale_bank import _nest_paths
    from repro.core.treepath import path_str
    from repro.models import registry
    from repro.optim.adamw import make_optimizer
    from repro.train import step as train_step
    from repro.train.serve import Engine

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=chip), tree)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if cells and w["name"] not in cells:
            continue
        conf = model.load_config(w["config"])
        tr = traffic.load(w["traffic"])
        cfg = model.builder(conf).program_config(conf)
        api = registry.build(cfg)
        params = model.abstract_params(cfg)
        progs = {}
        if tr["mode"] == "serve":
            n, cap = tr["pool"]["slots"], tr["pool"]["cache_len"]
            rows = tr["tasks"]["resident_rows"]
            flat = {}
            jax.tree_util.tree_map_with_path(
                lambda kp, l: flat.__setitem__(path_str(kp), l), params)
            stack = _nest_paths({
                p: jax.ShapeDtypeStruct(l.shape[:-2] + (rows,)
                                        + l.shape[-2:], l.dtype)
                for p, l in flat.items() if p.endswith(("/scale", "/zero"))})
            cache = jax.eval_shape(lambda: api.init_cache(n, cap))
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
            progs["decode"] = jax.jit(api.decode_step_slotted,
                                      donate_argnums=(2,)).lower(
                on_chip(params), on_chip(stack), on_chip(cache),
                on_chip(i32(n, 1)), on_chip(i32(n)), on_chip(i32(n)))
            top = Engine._bucket_len(tr["prompt"]["max"], cap)
            batch = {"tokens": i32(1, top), "last_pos": i32()}
            progs[f"prefill_{top}"] = jax.jit(api.prefill_slotted).lower(
                on_chip(params), on_chip(stack), on_chip(batch),
                on_chip(i32(1)))
        else:
            o = tr["optim"]
            ocfg = OptimConfig(lr=o["lr"], betas=tuple(o["betas"]),
                               eps=o["eps"], warmup_steps=1,
                               schedule="constant", grad_clip=o["grad_clip"])
            mask = policies.make_mask(params, cfg)
            opt = make_optimizer(ocfg, 1)
            state = {"params": params,
                     "opt": jax.eval_shape(lambda p: opt.init(p, mask),
                                           params),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
            b, s = tr["batch"], tr["seq_len"]
            batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            fn = train_step.build_train_step(api, cfg,
                                             TrainConfig(optim=ocfg), mask,
                                             opt)
            progs["train_step"] = fn.lower(on_chip(state), on_chip(batch))
        for name, lowered in progs.items():
            ma = lowered.compile().memory_analysis()
            gib = lambda x: round(x / 2 ** 30, 3)
            print(json.dumps({
                "cell": w["name"], "program": name,
                "argument_gib": gib(ma.argument_size_in_bytes),
                "output_gib": gib(ma.output_size_in_bytes),
                "alias_gib": gib(ma.alias_size_in_bytes),
                "temp_gib": gib(ma.temp_size_in_bytes),
                "peak_gib": gib(ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                - ma.alias_size_in_bytes
                                + ma.temp_size_in_bytes)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
