"""QLinear — the single fully-connected primitive, in every storage mode.

A linear layer is a param subtree; its mode is determined by which keys exist
(so the pytree itself carries the state machine and jit sees static shapes):

  fp       : {"w": (out, in) [, "b"]}
  peqa     : {"qw": packed codes, "scale": (out, G), "zero": (out, G) [, "b"]}
  qat      : {"w", "scale", "zero" [, "b"]}     — fake-quant STE on the fly
  (+ lora) : {"lora_a": (r, in), "lora_b": (out, r)} added to any of the above

`core/policies.py` performs the fp → peqa/qat/lora transformations; model
code only ever calls `apply` here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

import contextlib
import threading

from repro.core.quant import QuantSpec
from repro.kernels import ops

_tls = threading.local()


@contextlib.contextmanager
def reduce_precision_scope(enabled: bool):
    """Trace-time scope: all linears inside emit bf16 dot outputs (§Perf A1).
    Entered by registry.build wrappers when cfg.bf16_reduce is set."""
    prev = getattr(_tls, "bf16", False)
    _tls.bf16 = enabled
    try:
        yield
    finally:
        _tls.bf16 = prev


def init(rng, in_features: int, out_features: int, *, bias: bool = False,
         dtype=jnp.float32, std: Optional[float] = None) -> dict:
    std = std if std is not None else in_features ** -0.5
    p = {"w": (jax.random.normal(rng, (out_features, in_features)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((out_features,), dtype)
    return p


def _ste_round(x):
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def _fake_quant(w, scale, zero, spec: QuantSpec):
    """QAT forward: quantize-dequantize with straight-through rounding.
    Gradients flow to both w (STE, clipped) and scale/zero (analytic)."""
    n, m = w.shape
    g = scale.shape[-1]
    wg = w.reshape(n, g, m // g)
    s = scale[..., None].astype(w.dtype)
    z = zero[..., None].astype(w.dtype)
    q = _ste_round(wg / s) + z
    q = jnp.clip(q, 0, spec.levels)
    return (s * (q - z)).reshape(n, m)


class LayerOf:
    """Layer ``index`` of the layer-stacked array ``stack``, left in place.

    The decode layer scan hands each quantized linear its codes and scales
    this way (``transformer.decode_tokens``): the ops take the whole stack
    with ``layer=index`` and the kernels read that layer's blocks where
    they lie, so no layer is sliced out or copied.  ``shape``, ``ndim`` and
    ``dtype`` are the layer's; ``plain`` slices the layer out, for the few
    readers that need an array of their own."""
    __slots__ = ("stack", "index")

    def __init__(self, stack: jax.Array, index: jax.Array):
        self.stack, self.index = stack, index

    @property
    def shape(self) -> tuple:
        return self.stack.shape[1:]

    @property
    def ndim(self) -> int:
        return self.stack.ndim - 1

    @property
    def dtype(self):
        return self.stack.dtype


def plain(a):
    """``a`` as an array: a ``LayerOf`` sliced out, anything else as is."""
    if isinstance(a, LayerOf):
        return ops.at_layer(a.stack, a.index)
    return a


def plain_tree(tree):
    return jax.tree.map(plain, tree)


def in_place(*leaves) -> tuple:
    """(arrays, layer) for an op's ``layer=``: the stacks and their layer
    when every leaf is a ``LayerOf``, else the leaves as plain arrays and
    None."""
    if all(isinstance(a, LayerOf) for a in leaves):
        return tuple(a.stack for a in leaves), leaves[0].index
    return tuple(plain(a) for a in leaves), None


def slot_entry(slots, name: str):
    """Narrow a ``(task_ids, stack_subtree)`` pair to one child module.

    Returns None when there are no slots or the stacked-scale subtree has no
    entry for ``name`` (unquantized / EXCLUDE'd module) — the caller then
    takes the plain single-task path.
    """
    if slots is None:
        return None
    task_ids, subtree = slots
    if not isinstance(subtree, dict) or name not in subtree:
        return None
    return task_ids, subtree[name]


def apply(p: dict, x: jax.Array, spec: QuantSpec, *,
          lora_scale: float = 1.0, impl: Optional[str] = None,
          bf16_reduce: bool = False, slots=None,
          row_parallel: bool = False) -> jax.Array:
    """y = x W^T (+b) (+LoRA), storage-mode dispatched on present keys.

    bf16_reduce: emit the dot in the activation dtype (the MXU still
    accumulates f32 internally for bf16 inputs); halves the bytes of the
    TP collectives and of the matmul epilogue — §Perf change A1.

    slots: optional ``(task_ids (M,), {"scale": (T, out, G), "zero": …})``
    for the mixed-task decode step — each of the M rows of x (flattened
    leading dims) reads the scale row its slot's task owns.  Forward-only;
    ignored for non-peqa storage modes.  The codes and scales may be
    ``LayerOf`` views (the decode layer scan's), read in place.

    row_parallel: this layer contracts a mesh-sharded input dim
    (``dist.sharding.ROW_PARALLEL``: ``wo``, ``down``, ``out_proj``) — the
    quantized kernel then contracts the codes where they are stored."""
    bf16_reduce = bf16_reduce or getattr(_tls, "bf16", False)
    pet = None if bf16_reduce else jnp.float32
    if "qw" in p:
        if slots is not None and isinstance(slots[1], dict) \
                and "scale" in slots[1]:
            task_ids, stack = slots
            (qw, s, z), layer = in_place(p["qw"], stack["scale"],
                                         stack["zero"])
            y = ops.quant_matmul_slotted(
                x, qw, s, z, task_ids, spec, impl=impl,
                bf16_reduce=bf16_reduce, row_parallel=row_parallel,
                layer=layer)
        else:
            (qw, s, z), layer = in_place(p["qw"], p["scale"], p["zero"])
            y = ops.quant_matmul(x, qw, s, z, spec, impl=impl,
                                 bf16_reduce=bf16_reduce,
                                 row_parallel=row_parallel, layer=layer)
    elif "scale" in p:  # qat fake-quant (w present, scale learned)
        w = _fake_quant(p["w"].astype(x.dtype), p["scale"], p["zero"], spec)
        y = jnp.einsum("...k,nk->...n", x, w, preferred_element_type=pet
                       ).astype(x.dtype)
    else:
        y = jnp.einsum("...k,nk->...n", x, p["w"].astype(x.dtype),
                       preferred_element_type=pet).astype(x.dtype)
    if "lora_a" in p:
        a = p["lora_a"].astype(x.dtype)
        b = p["lora_b"].astype(x.dtype)
        y = y + lora_scale * jnp.einsum(
            "...r,nr->...n", jnp.einsum("...k,rk->...r", x, a), b,
            preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def out_features(p: dict) -> int:
    if "qw" in p or "scale" in p:
        return (p["qw"] if "qw" in p else p["w"]).shape[0]
    return p["w"].shape[0]
