"""The system under test, found by name from a configuration file.

A configuration file (``configs/<name>.json``) holds the published
``config.json`` keys as they are run, the quantization, the keys changed
from the source, and the names of two files of its own kind: its builder
(``builders/<builder>.py``: ``program_config`` and ``make_params``) and
its plain reference (``references/<reference>.py``).  What every family
shares lives here: the abstract PEQA tree and the per-task scale sets,
made on the host.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax

import named
import weights
from repro.core import policies
from repro.models import registry

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def builder(conf: dict):
    """The builder module a configuration names."""
    return named.module("builders", conf["builder"])


def abstract_params(cfg):
    """Shapes and dtypes of the program's PEQA tree (nothing allocated)."""
    api = registry.build(cfg)
    return jax.eval_shape(
        lambda r: policies.transform(api.init(r), cfg, r),
        jax.random.PRNGKey(0))


def task_sets(base: dict, seed: int, n_tasks: int) -> dict:
    """{task name: {scale path: array}} on the host.  ``base`` is the
    backbone's scale leaves by path (``extract_scales``); task ``t`` scales
    each by ``weights.task_multiplier(seed, t, linear name)``."""
    out = {}
    for t in range(n_tasks):
        out[task_name(t)] = {
            path: base[path] * weights.task_multiplier(
                seed, t, path.rsplit("/", 1)[0], base[path].shape)
            for path in base if path.endswith("/scale")}
    return out


def task_name(t: int) -> str:
    return f"task{t:04d}"
