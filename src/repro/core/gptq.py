"""OPTQ (GPTQ, Frantar et al. [28]) — the paper's PTQ baseline for the
LoRA+OPTQ arm of Tables 2/3.

Layer-wise second-order weight quantization: given a weight W (n, m) and the
Hessian H = 2 XᵀX of the layer's inputs, quantize columns left→right while
propagating the rounding error through Hinv (Cholesky form).  Scales/zeros
are the same per-channel RTN grid as PEQA's init, so PEQA-vs-OPTQ isolates
exactly what the paper isolates: error feedback from calibration data vs
end-to-end fine-tuning of the scales.

Calibration capture is implemented for the dense-transformer family (that is
what the paper's Table 2 models — GPT-Neo/J/LLaMA — all are): the block
structure is replayed layer by layer and every linear's true input stream is
collected (sequential quantization: later layers see the quantized prefix).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, QuantConfig
from repro.core.quant import QuantSpec, pack_codes, rtn_quantize
from repro.models import attention, common
from repro.models.common import apply_rope, rope_freqs


def gptq_quantize_matrix(w: np.ndarray, x: np.ndarray, qcfg: QuantConfig,
                         damp: float = 0.01):
    """GPTQ on one matrix. w (n, m), x (T, m) calibration inputs.

    Returns (q codes uint8 (n, m), scale (n, G), zero (n, G)).
    """
    w = np.asarray(w, np.float64)
    n, m = w.shape
    spec = qcfg.spec()
    g = spec.group_size or m

    h = 2.0 * (x.T.astype(np.float64) @ x.astype(np.float64))
    dead = np.diag(h) == 0
    h[dead, dead] = 1.0
    w[:, dead] = 0.0
    h += np.eye(m) * damp * np.mean(np.diag(h))
    hinv = np.linalg.cholesky(np.linalg.inv(h)).T      # upper triangular

    # fixed per-group RTN scales from the ORIGINAL weights (paper protocol)
    _, scale, zero = rtn_quantize(jnp.asarray(w, jnp.float32), spec,
                                  n_grid=qcfg.n_grid)
    scale = np.asarray(scale, np.float64)
    zero = np.asarray(zero, np.float64)

    q = np.zeros((n, m), np.uint8)
    wq = w.copy()
    for j in range(m):
        gj = j // g
        s, z = scale[:, gj], zero[:, gj]
        col = wq[:, j]
        qa = np.clip(np.round(col / s + z), 0, spec.levels)
        q[:, j] = qa.astype(np.uint8)
        deq = s * (qa - z)
        err = (col - deq) / hinv[j, j]
        if j + 1 < m:
            wq[:, j + 1:] -= np.outer(err, hinv[j, j + 1:])
    return q, scale.astype(np.float32), zero.astype(np.float32)


def _block_linear_inputs(layer_p: dict, h: jax.Array, cfg: ModelConfig):
    """Replay one dense-transformer block, returning each linear's input
    stream AND the block output (quantized weights already in layer_p are
    honored → sequential GPTQ)."""
    from repro.models import linear
    from repro.kernels import ops
    spec = cfg.quant.spec()
    b, s, _ = h.shape
    captures = {}
    hin = common.norm_apply(layer_p["ln1"], h, cfg)
    captures["attn/wq"] = captures["attn/wk"] = captures["attn/wv"] = hin
    q, k, v = attention._qkv(layer_p["attn"], hin, cfg)
    if cfg.use_rope:
        freqs = rope_freqs(cfg)
        pos = jnp.arange(s)
        q, k = apply_rope(q, pos, freqs), apply_rope(k, pos, freqs)
    o = ops.attention(q, k, v, causal=True, window=cfg.swa_window)
    o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
    captures["attn/wo"] = o
    h = h + linear.apply(layer_p["attn"]["wo"], o, spec, row_parallel=True)
    hin = common.norm_apply(layer_p["ln2"], h, cfg)
    captures["mlp/up"] = captures["mlp/gate"] = hin
    up = linear.apply(layer_p["mlp"]["up"], hin, spec)
    if "gate" in layer_p["mlp"]:
        gate = linear.apply(layer_p["mlp"]["gate"], hin, spec)
        act = jax.nn.silu(gate) * up
    else:
        act = jax.nn.gelu(up)
    captures["mlp/down"] = act
    h = h + linear.apply(layer_p["mlp"]["down"], act, spec, row_parallel=True)
    return captures, h


def gptq_quantize_transformer(params: dict, cfg: ModelConfig,
                              calib_tokens: jax.Array,
                              damp: float = 0.01, verbose: bool = False) -> dict:
    """Sequential OPTQ over a dense-transformer param tree (unstacked loop —
    calibration is offline and CPU-bound by design)."""
    qcfg = cfg.quant
    spec = qcfg.spec()
    n_layers = cfg.n_layers
    h = common.embed_apply(params["embed"], calib_tokens, cfg)

    def layer_slice(i):
        return jax.tree.map(lambda l: l[i], params["layers"])

    new_layers = []
    for i in range(n_layers):
        lp = layer_slice(i)
        captures, _ = _block_linear_inputs(lp, h, cfg)
        for name in ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                     "mlp/up", "mlp/gate", "mlp/down"):
            grp, key = name.split("/")
            if key not in lp[grp]:
                continue
            sub = lp[grp][key]
            if "w" not in sub:
                continue
            x = np.asarray(captures[name], np.float32).reshape(-1, sub["w"].shape[-1])
            qc, sc, zc = gptq_quantize_matrix(np.asarray(sub["w"]), x, qcfg, damp)
            newsub = {k: v for k, v in sub.items() if k != "w"}
            newsub.update(
                qw=pack_codes(jnp.asarray(qc)) if spec.packs else jnp.asarray(qc),
                scale=jnp.asarray(sc), zero=jnp.asarray(zc))
            lp[grp][key] = newsub
        # replay with quantized weights → next layer sees quantized stream
        _, h = _block_linear_inputs(lp, h, cfg)
        new_layers.append(lp)
        if verbose:
            print(f"[gptq] layer {i + 1}/{n_layers} done")

    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *new_layers)
    return dict(params, layers=stacked)
