"""The training loop: PEQA scales-only AdamW steps back to back through
``train.step.build_train_step``, the program's own jitted step, fed packed
documents from the mix's data file.

Set-up builds the one step object and its state and runs the first
``check_steps`` steps through it on distinct rows, keeping what the
comparison needs (each step's loss, the trainable leaves before the first
step and after the last, and the optimizer's first moment after step 1);
the window then continues with the same object and state.

``Job`` is the training mode of a benchmark run (``run.py`` finds it as
``<mode>_loop.Job`` from the mix's ``mode``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import traffic
from repro.configs.base import OptimConfig, TrainConfig
from repro.core import policies
from repro.core.treepath import path_str
from repro.optim.adamw import make_optimizer
from repro.train import step as train_step

Span = jax.profiler.TraceAnnotation


def trainable(tree, mask) -> dict:
    """{path: host array} of the trainable leaves of ``tree``."""
    out = {}
    jax.tree_util.tree_map_with_path(
        lambda kp, l, m: out.__setitem__(path_str(kp), np.asarray(l))
        if m else None, tree, mask)
    return out


class TrainLoop:
    def __init__(self, api, cfg, params, tr: dict, data: np.ndarray):
        o = tr["optim"]
        ocfg = OptimConfig(lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
                           weight_decay=0.0, warmup_steps=1,
                           schedule="constant", grad_clip=o["grad_clip"])
        self.mask = policies.make_mask(params, cfg)
        opt = make_optimizer(ocfg, total_steps=1)
        self.b1 = ocfg.betas[0]
        self.state = {"params": params, "opt": opt.init(params, self.mask),
                      "step": jnp.int32(0)}
        self.fn = train_step.build_train_step(api, cfg, TrainConfig(optim=ocfg),
                                              self.mask, opt)
        self.data = data
        self.tokens_per_step = data.shape[1] * (data.shape[2] - 1)
        self.i = 0

    def _batch(self, i: int) -> dict:
        rows = self.data[i % len(self.data)]
        return jax.device_put({"tokens": rows[:, :-1], "labels": rows[:, 1:]})

    def _step(self):
        with Span("train.step"):
            self.state, metrics = self.fn(self.state, self._batch(self.i))
        self.i += 1
        return metrics

    def _moments(self) -> dict:
        """{path: (first, second) moment} of the trainable leaves."""
        params = self.state["params"]
        flat, tdef = jax.tree_util.tree_flatten_with_path(params)
        mv = tdef.flatten_up_to(self.state["opt"]["mv"])
        return {path_str(kp): s for (kp, _), s, m in
                zip(flat, mv, jax.tree.leaves(self.mask)) if m}

    def check_steps(self, n: int) -> dict:
        """Run the first ``n`` steps (the first compiles); returns the
        program's readings for the comparison."""
        p0 = trainable(self.state["params"], self.mask)
        losses, m1 = [], None
        for k in range(n):
            metrics = self._step()
            losses.append(float(metrics["loss"]))
            if k == 0:
                m1 = {p: np.asarray(mv[0]) / (1 - self.b1)
                      for p, mv in self._moments().items()}
        pn = trainable(self.state["params"], self.mask)
        return {"losses": losses, "grad1": m1,
                "change": {p: pn[p] - p0[p] for p in p0}}

    def run(self, seconds: float, on_time=None) -> dict:
        """Steps back to back for ``seconds``, one step always queued behind
        the running one.  The step running at the close counts by the
        share of it that fell inside the window."""
        done = []
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0
        prev = self._step()
        while True:
            if on_time is not None:
                on_time(clock())
            nxt = self._step()
            with Span("train.wait"):
                jax.block_until_ready(prev["loss"])
            done.append(clock())
            prev = nxt
            if done[-1] >= seconds:
                break
        jax.block_until_ready(prev["loss"])
        inside = [t for t in done if t <= seconds]
        steps = float(len(inside))
        if len(done) > len(inside):
            last = inside[-1] if inside else 0.0
            steps += (seconds - last) / (done[len(inside)] - last)
        return {"completed": len(inside), "steps": steps, "ends": done,
                "window_s": seconds, "tokens": steps * self.tokens_per_step}


class Job:
    """One run of a training cell: the weights and rows from the seed, the
    step and its state, the first steps checked (set-up), the window, and
    the comparison of those first steps with the reference's own."""

    def __init__(self, cell, seed: int, seconds: float, mark):
        cfg, tr = cell.cfg, cell.tr
        self.cell, self.seed = cell, seed
        params = cell.builder.make_params(cfg, seed)
        jax.block_until_ready(params)
        mark("weights")
        data = traffic.train_tokens(tr, seed, cfg.vocab_size)
        self.loop = TrainLoop(cell.api, cfg, params, tr, data)
        del params
        mark("data and optimizer state")
        self.readings = check.by_linear(
            self.loop.check_steps(tr["check_steps"]))
        self.rows = data[:tr["check_steps"]]
        mark("checked steps")
        cell.log(f"losses {self.readings['losses']}")

    def run(self, seconds: float, on_time=None) -> dict:
        out = self.loop.run(seconds, on_time=on_time)
        out["attempted"] = out["completed"]
        return out

    def compare(self, ref, arch) -> dict:
        """Frees the program's state, then runs the reference's own steps on
        the checked rows."""
        self.loop = None
        self.want = check.ref_readings(ref, arch, self.seed, self.rows,
                                       self.cell.tr["optim"])
        self.details = {"losses": self.readings["losses"],
                        "ref_losses": self.want["losses"]}
        self.cell.log(f"check: {self.details}")
        return check.train_numbers(self.readings, self.want)

    def controls(self, ref, arch, lowp, faults: bool = True) -> dict:
        """After ``compare``: the control (the reference in ``lowp`` in the
        program's place) and, with ``faults``, the half-batch fault (half
        of each batch left out, the mean taken over the rest), planted in
        the reference in the program's place.  A step that returns its
        state unchanged reads 1 on the change by construction; one chip
        has no exchange."""
        opt = self.cell.tr["optim"]
        lo = check.ref_readings(ref, arch, self.seed, self.rows, opt, lowp)
        out = {"control": check.train_numbers(lo, self.want)}
        if faults:
            half = check.ref_readings(
                ref, arch, self.seed, self.rows[:, :self.rows.shape[1] // 2],
                opt)
            out["fault_half_batch"] = check.train_numbers(half, self.want)
        return out
