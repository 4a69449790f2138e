"""Mesh construction.

FUNCTIONS, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before its first jax call).

Every mesh in the repo comes from ``make_mesh``, which marks all axes
``Auto``: sharding is propagated by GSPMD from the shardings of the inputs
and the ``with_sharding_constraint`` hints in the model code.  (Since jax
0.7, ``jax.make_mesh`` defaults to ``Explicit`` axes, which refuse those
hints and require sharding-typed gathers.)

Topology notes (TPU v5e): 16×16 = 256 chips per pod; the multi-pod mesh adds
a leading 'pod' axis (DCN-connected).  'data' axes carry batch/DP, 'model'
carries Megatron-style TP (+ expert-parallel for MoE).  GSPMD emits
hierarchical collectives from the mesh order (pod outermost → cross-pod
reductions happen once per step on already-reduced values).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pod: int = 0):
    """Small mesh for in-process sharding tests (host devices)."""
    if pod:
        return make_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
