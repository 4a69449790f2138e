"""Builder of DeepSeek-V2 (``model_type: deepseek_v2``) from a configuration
file, named by the file's ``"builder": "deepseek_v2"``: the program's MoE
transformer with latent attention (``family="moe"``, ``cfg.mla``), YaRN
rope, leading dense blocks and dropless routed plus shared experts.

``program_config`` maps the published ``config.json`` keys onto the
program's ``ModelConfig``; ``make_params`` draws the quantized backbone on
the device in one jitted call, codes packed into the program's layout.
Layer ``l`` of a stack draws index ``l`` and expert ``e`` of MoE block
``l`` index ``l · E + e``, as ``references/deepseek_v2.py`` does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import model
import weights
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                QuantConfig, RopeScaling, TuningConfig)
from repro.core import quant
from repro.core.treepath import path_str


def program_config(c: dict, *, n_layers: int | None = None,
                   vocab: int | None = None, d_model: int | None = None,
                   d_ff: int | None = None, heads: tuple | None = None
                   ) -> ModelConfig:
    """``ModelConfig`` of a configuration file.  The keyword overrides exist
    for the CPU rehearsal (tests), which runs the same file at a tiny size
    (with ``d_model``, the latent and head widths follow it as the
    reference's ``tiny_widths`` has them; ``n_layers=2`` keeps one dense
    and one MoE block); a benchmark run passes none of them."""
    if c.get("q_lora_rank") is not None:
        raise ValueError("the program's MLA has no query latent "
                         "(q_lora_rank must be null)")
    if c.get("topk_method", "greedy") != "greedy" or c.get("n_group", 1) != 1:
        raise ValueError("only greedy top-k routing over one group is run")
    q, rs = c["quant"], c["rope_scaling"]
    nh = heads[0] if heads else c["num_attention_heads"]
    d = d_model or c["hidden_size"]
    w = dict(kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
             rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
             moe_ff=c["moe_intermediate_size"],
             experts=c["n_routed_experts"])
    dense_ff = d_ff or c["intermediate_size"]
    if d_model:
        hd = d // nh
        w.update(kv_lora=d // 2, nope=hd, rope=hd // 2, v=hd,
                 moe_ff=dense_ff // 4, experts=8)
    return ModelConfig(
        name=c["name"], family="moe",
        n_layers=n_layers or c["num_hidden_layers"], d_model=d,
        n_heads=nh, n_kv_heads=nh, head_dim=w["v"], d_ff=dense_ff,
        vocab_size=vocab or c["vocab_size"], rope_theta=float(c["rope_theta"]),
        act="silu", norm_type="rmsnorm", norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], remat="block",
        max_seq=c["max_position_embeddings"],
        n_dense_layers=c["first_k_dense_replace"],
        mla=MLAConfig(kv_lora_rank=w["kv_lora"], qk_nope_head_dim=w["nope"],
                      qk_rope_head_dim=w["rope"], v_head_dim=w["v"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        moe=MoEConfig(n_experts=w["experts"], top_k=c["num_experts_per_tok"],
                      n_shared_experts=c["n_shared_experts"],
                      d_ff_expert=w["moe_ff"], expert_sharding="expert",
                      norm_topk_prob=bool(c["norm_topk_prob"]),
                      routed_scaling_factor=float(
                          c["routed_scaling_factor"])),
        quant=QuantConfig(bits=q["bits"], group_size=q["group_size"],
                          layout=q["layout"]),
        tuning=TuningConfig(mode="peqa"))


def _by_index(shape, n_experts: int, fn):
    """Draw a (L, [E,] …) leaf: ``fn(index)`` of layer l (and expert e) at
    index l (l · E + e)."""
    layers = jnp.arange(shape[0])
    if n_experts:      # a layer's experts at once: few, large draws
        return jax.lax.map(lambda l: jax.vmap(
            lambda e: fn(l * n_experts + e))(jnp.arange(n_experts)), layers)
    return jax.lax.map(fn, layers)


def _leaf(root, path: str, leaf, cfg: ModelConfig, k_of: dict):
    """Draw one leaf of the PEQA tree."""
    spec = cfg.quant.spec()
    bits = spec.bits
    parent, key = path.rsplit("/", 1)
    shape, dtype = leaf.shape, leaf.dtype
    if path == "embed/emb":
        return weights.embedding(root, *shape).astype(dtype)
    if path == "lm_head/w":
        return weights.head(root, *shape).astype(dtype)
    if not path.startswith("layers/"):        # final norm
        return weights.norm_gain(root, parent, 0, shape[0]).astype(dtype)
    e_n = cfg.moe.n_experts if "/experts" in parent else 0
    if key == "qw":
        n, k = shape[-2], k_of[parent]

        def one(i):
            q = weights.codes(root, parent, i, n, k, bits)
            return (quant.pack_codes_planes(q, bits) if spec.plane
                    else quant.pack_codes(q))
        return _by_index(shape, e_n, one).astype(dtype)
    if key in ("scale", "zero"):
        n, g = shape[-2:]
        k = k_of[parent]
        fn = ((lambda i: weights.scale(root, parent, i, n, g, k, bits))
              if key == "scale"
              else (lambda i: weights.zero(root, parent, i, n, g, bits)))
        return _by_index(shape, e_n, fn).astype(dtype)
    if parent.endswith("router"):
        e, d = shape[-2:]
        rows = lambda l: jax.vmap(lambda i: weights.bias(
            root, path, l * e + i, d))(jnp.arange(e))
        return (jax.lax.map(rows, jnp.arange(shape[0]))
                * (d ** -0.5 / 0.02)).astype(dtype)
    # RMSNorm gains (ln1, ln2, attn/kv_norm)
    return jax.lax.map(lambda l: weights.norm_gain(root, parent, l,
                                                   shape[-1]),
                       jnp.arange(shape[0])).astype(dtype)


def make_params(cfg: ModelConfig, seed: int):
    """The program's PEQA tree for ``seed``, drawn on the device in one
    jitted call, in the types the program serves and tunes it in."""
    abstract = model.abstract_params(cfg)
    fields = cfg.quant.spec().fields
    k_of = {path_str(kp).rsplit("/", 1)[0]: leaf.shape[-1] * fields
            for kp, leaf in jax.tree_util.tree_leaves_with_path(abstract)
            if path_str(kp).endswith("/qw")}

    def build(root):
        return jax.tree_util.tree_map_with_path(
            lambda kp, l: _leaf(root, path_str(kp), l, cfg, k_of), abstract)
    return jax.jit(build)(weights.root_key(seed))
