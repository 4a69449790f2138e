"""Builder of the program's dense transformer (``family="dense"``) from a
configuration file, named by the file's ``"builder": "dense"``.

``program_config`` maps the published ``config.json`` keys onto the
program's ``ModelConfig``; ``make_params`` draws the quantized backbone on
the device in one jitted call, codes packed into the program's layout,
without ever holding the float model.  A builder for another family is a
file beside this one with the same two functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import model
import weights
from repro.configs.base import ModelConfig, QuantConfig, TuningConfig
from repro.core import quant
from repro.core.treepath import path_str


def program_config(c: dict, *, n_layers: int | None = None,
                   vocab: int | None = None, d_model: int | None = None,
                   d_ff: int | None = None, heads: tuple | None = None
                   ) -> ModelConfig:
    """``ModelConfig`` of a configuration file.  The keyword overrides exist
    for the CPU rehearsal (tests), which runs the same file at a tiny size;
    a benchmark run passes none of them."""
    if c.get("use_bias") not in (None, False, "qkv"):
        raise ValueError(f"the program carries biases on q, k, v only; "
                         f"use_bias={c['use_bias']!r} cannot be run")
    q = c["quant"]
    nh, nkv = heads or (c["num_attention_heads"], c["num_key_value_heads"])
    d = d_model or c["hidden_size"]
    head_dim = c.get("head_dim") or c["hidden_size"] // c[
        "num_attention_heads"]
    if d_model:
        head_dim = d // nh
    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=n_layers or c["num_hidden_layers"], d_model=d,
        n_heads=nh, n_kv_heads=nkv, head_dim=head_dim,
        d_ff=d_ff or c["intermediate_size"],
        vocab_size=vocab or c["vocab_size"], rope_theta=float(c["rope_theta"]),
        qkv_bias=c.get("use_bias") == "qkv",
        act="gelu" if c["hidden_act"].startswith("gelu") else "silu",
        norm_type=("layernorm" if c.get("norm_type") == "layer_norm"
                   else "rmsnorm"),
        norm_eps=float(c.get("norm_epsilon", c.get("rms_norm_eps", 1e-5))),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        swa_window=c.get("sliding_window"),
        dtype=c["torch_dtype"], remat="block",
        max_seq=c["max_position_embeddings"],
        quant=QuantConfig(bits=q["bits"], group_size=q["group_size"],
                          layout=q["layout"]),
        tuning=TuningConfig(mode="peqa"))


def _leaf(root, path: str, leaf, cfg: ModelConfig):
    """Draw one leaf of the PEQA tree; layer-stacked leaves by layer."""
    spec = cfg.quant.spec()
    bits = spec.bits
    parent, key = path.rsplit("/", 1)
    shape, dtype = leaf.shape, leaf.dtype
    if path == "embed/emb":
        return weights.embedding(root, *shape).astype(dtype)
    if path == "lm_head/w":
        return weights.head(root, *shape).astype(dtype)
    if not path.startswith("layers/"):        # final norm
        fn = weights.norm_gain if key == "g" else weights.norm_bias
        return fn(root, parent, 0, shape[0]).astype(dtype)
    layers = jnp.arange(shape[0])
    if key == "qw":
        n = shape[-2]
        k = shape[-1] * spec.fields

        def one(layer):
            q = weights.codes(root, parent, layer, n, k, bits)
            if spec.plane:
                return quant.pack_codes_planes(q, bits)
            return quant.pack_codes(q)
        return jax.lax.map(one, layers).astype(dtype)
    if key in ("scale", "zero"):
        n, g = shape[-2:]
        k = _in_features(parent, cfg)
        if key == "scale":
            fn = lambda l: weights.scale(root, parent, l, n, g, k, bits)
        else:
            fn = lambda l: weights.zero(root, parent, l, n, g, bits)
        return jax.vmap(fn)(layers).astype(dtype)
    if parent.endswith(("ln1", "ln2")):
        fn = weights.norm_gain if key == "g" else weights.norm_bias
    else:
        fn = weights.bias
    return jax.vmap(lambda l: fn(root, parent, l, shape[-1]))(layers).astype(
        dtype)


def _in_features(linear: str, cfg: ModelConfig) -> int:
    name = linear.rsplit("/", 1)[-1]
    if name == "wo":
        return cfg.n_heads * cfg.d_head
    if name == "down":
        return cfg.d_ff
    return cfg.d_model


def make_params(cfg: ModelConfig, seed: int):
    """The program's PEQA tree for ``seed``, drawn on the device in one
    jitted call, in the types the program serves and tunes it in."""
    abstract = model.abstract_params(cfg)

    def build(root):
        return jax.tree_util.tree_map_with_path(
            lambda kp, l: _leaf(root, path_str(kp), l, cfg), abstract)
    return jax.jit(build)(weights.root_key(seed))
