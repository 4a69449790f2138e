"""GPipe-style pipeline parallelism over ``shard_map`` + ``ppermute``.

``pipeline_apply(layer_fn, stacked_ws, x, mesh)`` runs ``L`` stacked layers
as ``S`` pipeline stages (S = mesh size along the pipeline axis, L/S layers
per stage, weights sharded on the layer dim so each stage only ever holds
its own slice).  The batch is split into ``S`` microbatches and streamed
through the classic GPipe schedule: at step ``t`` stage ``s`` processes
microbatch ``t − s``, then hands its activation to stage ``s+1`` with a
single ring ``ppermute``.  Total steps ``T = M + S − 1``; the (S−1)/T
bubble is the standard GPipe cost.

Everything inside is differentiable JAX (scan / where / ppermute / psum), so
``jax.grad`` through a pipelined forward matches the sequential
``lax.scan`` reference exactly — the transpose of the ring permute is the
reverse ring, and dead schedule slots (bubble steps, discarded final
carries) receive zero cotangent.  Pinned by
``tests/test_sharding.py::test_pipeline_parallel_subprocess``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _pipeline_axis(mesh) -> str:
    if "stage" in mesh.axis_names:
        return "stage"
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh axes {tuple(mesh.axis_names)} have no 'stage' axis; pass "
        "axis_name= explicitly (silently pipelining over a data/tensor "
        "axis would destroy that axis's parallelism)")


def pipeline_apply(layer_fn, stacked_ws, x: jax.Array, mesh,
                   axis_name: str | None = None) -> jax.Array:
    """Apply ``L`` stacked layers to ``x`` (batch, ...) as a pipeline.

    ``layer_fn(w_i, h) -> h`` must preserve ``h``'s shape (residual-stream
    layers).  ``stacked_ws`` is an array or pytree whose leaves all have the
    layer dim leading.
    """
    axis_name = axis_name or _pipeline_axis(mesh)
    n_stages = dict(mesh.shape)[axis_name]
    n_layers = jax.tree.leaves(stacked_ws)[0].shape[0]
    batch = x.shape[0]
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers do not divide {n_stages} stages")
    if batch % n_stages:
        raise ValueError(f"batch {batch} does not divide {n_stages} "
                         "microbatches (one per stage)")
    n_micro = n_stages
    mub = batch // n_micro
    n_steps = n_micro + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fn(w_local, x_full):
        s_idx = jax.lax.axis_index(axis_name)
        xm = x_full.reshape(n_micro, mub, *x_full.shape[1:])

        def apply_local(h):
            h, _ = jax.lax.scan(lambda hh, w: (layer_fn(w, hh), None),
                                h, w_local)
            return h

        def step(carry, t):
            cur, outs = carry
            feed = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            # stage 0 injects a fresh microbatch; everyone else continues
            # what arrived over the ring last step
            out = apply_local(jnp.where(s_idx == 0, feed, cur))
            # the last stage banks finished microbatch m = t - (S-1)
            m = t - (n_stages - 1)
            idx = jnp.clip(m, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            done = jnp.where((s_idx == n_stages - 1) & (m >= 0), out, prev)
            outs = jax.lax.dynamic_update_index_in_dim(outs, done, idx, 0)
            return (jax.lax.ppermute(out, axis_name, ring), outs), None

        cur0 = jnp.zeros((mub, *x_full.shape[1:]), x_full.dtype)
        outs0 = jnp.zeros((n_micro, mub, *x_full.shape[1:]), x_full.dtype)
        (_, outs), _ = jax.lax.scan(step, (cur0, outs0),
                                    jnp.arange(n_steps))
        y = outs.reshape(x_full.shape)
        # only the last stage holds real outputs; psum broadcasts them so the
        # replicated out_spec holds (and transposes to a clean mask in grad)
        y = jnp.where(s_idx == n_stages - 1, y, jnp.zeros_like(y))
        return jax.lax.psum(y, axis_name)

    w_specs = jax.tree.map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stacked_ws)
    x_spec = P(*([None] * x.ndim))
    return shard_map(stage_fn, mesh=mesh, in_specs=(w_specs, x_spec),
                     out_specs=x_spec, check_vma=False)(stacked_ws, x)
