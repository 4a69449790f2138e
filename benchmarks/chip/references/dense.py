"""Plain float32 reference of a dense decoder-only transformer with PEQA
weights: ``W = s · (q − z)`` per output channel and group of input columns.

Written from the published description (pre-norm blocks, rotary position
embedding on halves of each head, grouped-query attention, a GELU MLP or a
SiLU-gated one, LayerNorm or RMSNorm, untied head) and from nothing of the
program under test: weights are drawn here from the seed through
``weights.py``, layer by layer, so the float model is never whole in
memory.  Every contraction is float32 at the highest matmul precision.

``lowp`` names a lower precision (``"float8_e4m3fn"``): every matmul
operand, weights and activations, is rounded to it first, with float32
accumulation.  That is the control: the reference in the program's place,
one precision step below what the configuration states (bfloat16).

Departures, shared with the program: q, k and v carry biases and no other
linear does; packed training rows attend across document boundaries (the
program's causal mask covers the whole row).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    gated: bool
    layernorm: bool
    eps: float
    rope_theta: float
    qkv_bias: bool
    bits: int
    group: int | None

    @staticmethod
    def of(c: dict, **over) -> "Arch":
        """From a configuration file; ``over`` shrinks it for CPU tests."""
        kw = dict(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or (c["hidden_size"]
                                           // c["num_attention_heads"]),
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            layers=c["num_hidden_layers"],
            gated=not c["hidden_act"].startswith("gelu"),
            layernorm=c.get("norm_type") == "layer_norm",
            eps=float(c.get("norm_epsilon", c.get("rms_norm_eps", 1e-5))),
            rope_theta=float(c["rope_theta"]),
            qkv_bias=c.get("use_bias") == "qkv",
            bits=c["quant"]["bits"], group=c["quant"]["group_size"])
        kw.update(over)
        if "d" in over and "head_dim" not in over:
            kw["head_dim"] = kw["d"] // kw["heads"]
        return Arch(**kw)

    def linears(self) -> dict:
        """{name: (out features, in features)} of one layer."""
        hd = self.heads * self.head_dim
        kvd = self.kv_heads * self.head_dim
        out = {"attn/wq": (hd, self.d), "attn/wk": (kvd, self.d),
               "attn/wv": (kvd, self.d), "attn/wo": (self.d, hd),
               "mlp/up": (self.d_ff, self.d), "mlp/down": (self.d, self.d_ff)}
        if self.gated:
            out["mlp/gate"] = (self.d_ff, self.d)
        return out

    def groups(self, k: int) -> int:
        return 1 if self.group is None else k // self.group


def _round(x, lowp):
    if lowp is None:
        return x.astype(jnp.float32)
    return x.astype(jnp.dtype(lowp)).astype(jnp.float32)


def _mm(a, b, lowp, spec):
    return jnp.einsum(spec, _round(a, lowp), _round(b, lowp),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def base_scales(arch: Arch, root) -> dict:
    """{linear: (L, N, G)} scales of the backbone, as drawn from the seed."""
    out = {}
    for name, (n, k) in arch.linears().items():
        full = "layers/" + name
        out[name] = jax.vmap(lambda l: weights.scale(
            root, full, l, n, arch.groups(k), k, arch.bits))(
                jnp.arange(arch.layers))
    return out


def _dequant(arch: Arch, root, name: str, layer, scale):
    n, k = arch.linears()[name]
    g = arch.groups(k)
    full = "layers/" + name
    q = weights.codes(root, full, layer, n, k, arch.bits).astype(jnp.float32)
    z = weights.zero(root, full, layer, n, g, arch.bits)
    w = scale[..., None] * (q.reshape(n, g, k // g) - z[..., None])
    return w.reshape(n, k)


def _norm(arch: Arch, root, name: str, layer, x):
    g = weights.norm_gain(root, name, layer, arch.d)
    if arch.layernorm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + arch.eps) * g + \
            weights.norm_bias(root, name, layer, arch.d)
    return x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + arch.eps) * g


def _rope(arch: Arch, x, pos):
    dh = arch.head_dim
    freqs = 1.0 / (arch.rope_theta ** (jnp.arange(0, dh, 2,
                                                  dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(arch: Arch, q, k, v, lowp):
    """Causal grouped-query attention of one sequence; q (S, H, D),
    k/v (S, Hkv, D).  One key-value head's query group at a time."""
    s = q.shape[0]
    rep = arch.heads // arch.kv_heads
    qg = q.reshape(s, arch.kv_heads, rep, arch.head_dim).transpose(1, 0, 2, 3)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one(args):
        qh, kh, vh = args                       # (S, rep, D), (S, D), (S, D)
        lg = _mm(qh, kh, lowp, "qrd,kd->rqk") * arch.head_dim ** -0.5
        lg = jnp.where(causal[None], lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        return _mm(p, vh, lowp, "rqk,kd->qrd")
    o = jax.lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(s, arch.heads * arch.head_dim)


def _block(arch: Arch, root, layer, h, scales, lowp):
    """One pre-norm block over one sequence h (S, d); ``scales`` holds this
    layer's (N, G) scales of every linear."""
    s = h.shape[0]
    lin = lambda name, x: _mm(x, _dequant(arch, root, name, layer,
                                          scales[name]), lowp, "sk,nk->sn")
    x = _norm(arch, root, "layers/ln1", layer, h)
    q, k, v = (lin("attn/" + w, x) for w in ("wq", "wk", "wv"))
    if arch.qkv_bias:
        q = q + weights.bias(root, "layers/attn/wq", layer, q.shape[-1])
        k = k + weights.bias(root, "layers/attn/wk", layer, k.shape[-1])
        v = v + weights.bias(root, "layers/attn/wv", layer, v.shape[-1])
    pos = jnp.arange(s)
    q = _rope(arch, q.reshape(s, arch.heads, arch.head_dim), pos)
    k = _rope(arch, k.reshape(s, arch.kv_heads, arch.head_dim), pos)
    v = v.reshape(s, arch.kv_heads, arch.head_dim)
    h = h + lin("attn/wo", _attention(arch, q, k, v, lowp))
    x = _norm(arch, root, "layers/ln2", layer, h)
    if arch.gated:
        m = jax.nn.silu(lin("mlp/gate", x)) * lin("mlp/up", x)
    else:
        m = jax.nn.gelu(lin("mlp/up", x), approximate=True)
    return h + lin("mlp/down", m)


def hidden(arch: Arch, root, tokens, scales, lowp=None, remat=False):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,);
    ``scales``: {linear: (L, N, G)}."""
    h = weights.embedding(root, arch.vocab, arch.d)[tokens]

    def body(h, xs):
        layer, sc = xs
        return _block(arch, root, layer, h, sc, lowp), None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, (jnp.arange(arch.layers), scales))
    return _norm(arch, root, "final_norm", 0, h)


def logits(arch: Arch, root, h, lowp=None):
    """Head logits (…, V) of hidden rows h (…, d)."""
    return _mm(h, weights.head(root, arch.vocab, arch.d), lowp,
               "...d,vd->...v")


@partial(jax.jit, static_argnums=(0, 5))
def served_logits(arch: Arch, seed_key, tokens, rows, scales, lowp=None):
    """Reference logits at positions ``rows`` of one sequence."""
    h = hidden(arch, seed_key, tokens, scales, lowp)
    return logits(arch, seed_key, h[rows], lowp)


def nll_sum(arch: Arch, root, scales, tokens, labels, lowp=None):
    """Summed next-token cross entropy of one row ``tokens`` (S,) against
    ``labels`` (S,); differentiable in ``scales`` ({linear: (L, N, G)}).
    Rows are taken one call at a time (``row_loss_and_grad``): under a map
    over rows, autodiff would keep every layer's dequantized weights."""
    h = hidden(arch, root, tokens, scales, lowp, remat=True)
    lg = logits(arch, root, h, lowp)
    return (jax.nn.logsumexp(lg, -1)
            - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]).sum()


@partial(jax.jit, static_argnums=(0, 5))
def row_loss_and_grad(arch: Arch, root, scales, tokens, labels, lowp=None):
    return jax.value_and_grad(lambda s: nll_sum(arch, root, s, tokens,
                                                labels, lowp))(scales)


def adamw(grads: dict, state: dict, params: dict, opt: dict):
    """One step of AdamW with global-norm clipping and a constant rate (the
    cell's optimizer, as its traffic file states it), on host float64."""
    b1, b2 = opt["betas"]
    t = state["t"] + 1
    gn = np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                     for g in grads.values()))
    clip = min(1.0, opt["grad_clip"] / (gn + 1e-9))
    new_p, m, v = {}, {}, {}
    for k, g in grads.items():
        g = np.asarray(g, np.float64) * clip
        m[k] = b1 * state["m"][k] + (1 - b1) * g
        v[k] = b2 * state["v"][k] + (1 - b2) * g * g
        upd = (m[k] / (1 - b1 ** t)) / (np.sqrt(v[k] / (1 - b2 ** t))
                                        + opt["eps"])
        new_p[k] = params[k] - opt["lr"] * upd
    return new_p, {"t": t, "m": m, "v": v}, clip
