"""Plain float32 reference of DeepSeek-V2 (``model_type: deepseek_v2``) with
PEQA weights: ``W = s · (q − z)`` per output channel and group of input
columns.

Written from the published equations (HF ``modeling_deepseek``) and from
nothing of the program under test; weights are drawn here from the seed
through ``weights.py``, layer by layer and expert by expert, so the float
model is never whole in memory.  Every contraction is float32 at the
highest matmul precision.  Per block, RMSNorm pre-norms (eps from the
file):

- Attention (MLA, ``q_lora_rank`` null): ``[q_nope, q_pe]_h =
  (W_q x)_h``; ``[c, k_pe] = W_kva x``, ``c ← RMSNorm(c)``;
  ``[k_nope, v]_h = (W_kvb c)_h``;
  ``k_pe`` one rotary key shared by every head; scores
  ``σ · [q_nope, rope(q_pe)] · [k_nope, rope(k_pe)]`` with
  ``σ = (dn + dr)^-½ · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``;
  causal softmax; ``o = W_o · concat_h(Σ p v_h)``.
- Rope: YaRN over the ``dr`` rope dims (θ, ``factor``, original context,
  ``beta_fast``, ``beta_slow`` as published), cos and sin times
  ``m(mscale) / m(mscale_all_dim)``.
- FFN: the first ``first_k_dense_replace`` blocks a SiLU-gated MLP of
  ``intermediate_size``; the rest
  ``y = Σ_{k∈topK} p_k · E_k(x) + S(x)``, ``p = softmax(W_g x)`` in float32
  over every routed expert, the top K's weights renormalised only with
  ``norm_topk_prob`` and else times ``routed_scaling_factor``; each
  ``E_k`` a SiLU-gated MLP of ``moe_intermediate_size``, ``S`` the shared
  experts as one gated MLP of ``n_shared_experts`` times that width.  Every
  expert is computed for every token and weighed by its gate weight (zero
  off the top K), which is the routed sum exactly.

``lowp`` names a lower precision (``"float8_e4m3fn"``): every matmul
operand, weights and activations, the router's among them, is rounded to
it first, with float32 accumulation.  That is the control.

Departures, shared with the program:
- rope turns the halves of each rope slice, where the published model
  turns interleaved pairs (it permutes each slice's dims to halves first):
  a fixed permutation of ``W_q``'s and ``W_kva``'s rope rows, which random
  weights make equivalent;
- the router's weights are random rows of norm about 1 (``weights.bias``
  rescaled), and no auxiliary loss is applied (the aux loss plays no part
  in serving);
- packed training rows attend across document boundaries.

Leaf names follow the program's tree (``layers/...``): the leading dense
blocks under ``layers/dense/``, the MoE blocks' attention under
``layers/attn/``, their experts under ``layers/moe/experts_ep/`` and
shared experts under ``layers/moe/shared/``.  Layer ``l`` of a stack draws
index ``l``; expert ``e`` of MoE block ``l`` draws index ``l · E + e``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights

EXPERTS = "moe/experts_ep"
SHARED = "moe/shared"


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    d_ff: int
    moe_ff: int
    experts: int
    top_k: int
    shared: int
    vocab: int
    layers: int
    dense_layers: int
    eps: float
    theta: float
    yarn: tuple          # (factor, original, beta_fast, beta_slow, mscale,
    #                       mscale_all_dim)
    norm_topk: bool
    routed_scale: float
    bits: int
    group: int | None

    @staticmethod
    def of(c: dict, **over) -> "Arch":
        """From a configuration file.  ``over`` takes ``run.arch_sizes``'
        keys (d, heads, kv_heads, d_ff, vocab, layers) and shrinks the
        model for CPU tests as ``builders/deepseek_v2.py`` does: with ``d``
        given, every latent and head width follows from ``d`` and
        ``heads`` (``tiny_widths``)."""
        rs = c["rope_scaling"]
        kw = dict(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
            d_ff=c["intermediate_size"], moe_ff=c["moe_intermediate_size"],
            experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            shared=c["n_shared_experts"], vocab=c["vocab_size"],
            layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"],
            eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
            yarn=(float(rs["factor"]),
                  int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"])),
            norm_topk=bool(c["norm_topk_prob"]),
            routed_scale=float(c["routed_scaling_factor"]),
            bits=c["quant"]["bits"], group=c["quant"]["group_size"])
        over = dict(over)
        over.pop("kv_heads", None)          # MLA: every head has its keys
        if "d" in over:
            kw.update(tiny_widths(over["d"], over.get("heads", kw["heads"]),
                                  over.get("d_ff", kw["d_ff"])))
        kw.update(over)
        return Arch(**kw)

    def linears(self, stack: str) -> dict:
        """{name: (out features, in features, experts or 0)} of one block
        of ``stack`` (``"dense"`` or ``"moe"``)."""
        h = self.heads
        pre = "dense/" if stack == "dense" else ""
        out = {pre + "attn/wq": (h * (self.nope + self.rope), self.d, 0),
               pre + "attn/wkv_a": (self.kv_lora + self.rope, self.d, 0),
               pre + "attn/wkv_b": (h * (self.nope + self.v), self.kv_lora,
                                    0),
               pre + "attn/wo": (self.d, h * self.v, 0)}
        if stack == "dense":
            out.update({"dense/mlp/gate": (self.d_ff, self.d, 0),
                        "dense/mlp/up": (self.d_ff, self.d, 0),
                        "dense/mlp/down": (self.d, self.d_ff, 0)})
            return out
        e, f, sf = self.experts, self.moe_ff, self.moe_ff * self.shared
        out.update({EXPERTS + "/gate": (f, self.d, e),
                    EXPERTS + "/up": (f, self.d, e),
                    EXPERTS + "/down": (self.d, f, e)})
        if self.shared:
            out.update({SHARED + "/gate": (sf, self.d, 0),
                        SHARED + "/up": (sf, self.d, 0),
                        SHARED + "/down": (self.d, sf, 0)})
        return out

    def depth(self, stack: str) -> int:
        return (self.dense_layers if stack == "dense"
                else self.layers - self.dense_layers)

    def groups(self, k: int) -> int:
        return 1 if self.group is None else k // self.group


def tiny_widths(d: int, heads: int, d_ff: int) -> dict:
    """The CPU rehearsal's widths for hidden size ``d``: a latent of d/2,
    heads of d/heads (rope half that), experts a quarter of ``d_ff``, 8
    routed experts."""
    hd = d // heads
    return dict(kv_lora=d // 2, nope=hd, rope=hd // 2, v=hd,
                moe_ff=d_ff // 4, experts=8)


def _round(x, lowp):
    if lowp is None:
        return x.astype(jnp.float32)
    return x.astype(jnp.dtype(lowp)).astype(jnp.float32)


def _mm(a, b, lowp, spec):
    return jnp.einsum(spec, _round(a, lowp), _round(b, lowp),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _draw_scales(arch: Arch, root, name: str, n: int, k: int, e: int,
                 depth: int):
    full = "layers/" + name
    g = arch.groups(k)
    one = lambda i: weights.scale(root, full, i, n, g, k, arch.bits)
    layers = jnp.arange(depth)
    if not e:
        return jax.vmap(one)(layers)
    return jax.vmap(lambda l: jax.vmap(lambda x: one(l * e + x))(
        jnp.arange(e)))(layers)


def base_scales(arch: Arch, root) -> dict:
    """{linear: (L, [E,] N, G)} scales of the backbone, as drawn."""
    out = {}
    for stack in ("dense", "moe"):
        for name, (n, k, e) in arch.linears(stack).items():
            out[name] = _draw_scales(arch, root, name, n, k, e,
                                     arch.depth(stack))
    return out


def _dequant(arch: Arch, root, name: str, index, scale, n: int, k: int):
    g = arch.groups(k)
    full = "layers/" + name
    q = weights.codes(root, full, index, n, k, arch.bits).astype(jnp.float32)
    z = weights.zero(root, full, index, n, g, arch.bits)
    w = scale[..., None] * (q.reshape(n, g, k // g) - z[..., None])
    return w.reshape(n, k)


def _rms(arch: Arch, root, name: str, layer, x, width: int):
    g = weights.norm_gain(root, name, layer, width)
    return x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + arch.eps) * g


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(arch: Arch) -> np.ndarray:
    """YaRN inverse frequencies of the rope dims (HF
    ``DeepseekV2YarnRotaryEmbedding``)."""
    factor, orig, fast, slow, _, _ = arch.yarn
    dim, base = arch.rope, arch.theta

    def corr(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(base)))
    lo = max(math.floor(corr(fast)), 0)
    hi = min(math.ceil(corr(slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / (hi - lo),
                   0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(arch: Arch, x, pos):
    """Rope on halves of the last dim of x (S, ..., dr)."""
    factor, _, _, _, ms, ms_all = arch.yarn
    m = yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(arch))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (arch.rope // 2,)
    cos, sin = (m * jnp.cos(ang)).reshape(shape), (m * jnp.sin(ang)).reshape(
        shape)
    x1, x2 = x[..., :arch.rope // 2], x[..., arch.rope // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(arch: Arch) -> float:
    return ((arch.nope + arch.rope) ** -0.5
            * yarn_mscale(arch.yarn[0], arch.yarn[5]) ** 2)


def _attention(arch: Arch, root, pre: str, layer, x, scales, lowp):
    """MLA over one sequence x (S, d); ``pre`` is the stack's name prefix."""
    s, h = x.shape[0], arch.heads
    lin = _linear(arch, root, layer, scales, lowp)
    q = lin(pre + "attn/wq", x).reshape(s, h, arch.nope + arch.rope)
    kva = lin(pre + "attn/wkv_a", x)
    c = _rms(arch, root, "layers/" + pre + "attn/kv_norm", layer,
             kva[:, :arch.kv_lora], arch.kv_lora)
    kv = lin(pre + "attn/wkv_b", c).reshape(s, h, arch.nope + arch.v)
    pos = jnp.arange(s)
    q_pe = _rope(arch, q[..., arch.nope:], pos)
    k_pe = _rope(arch, kva[:, arch.kv_lora:], pos)                 # (S, dr)
    lg = (_mm(q[..., :arch.nope], kv[..., :arch.nope], lowp,
              "qhd,khd->hqk")
          + _mm(q_pe, k_pe, lowp, "qhd,kd->hqk")) * softmax_scale(arch)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], lg, -jnp.inf), axis=-1)
    o = _mm(p, kv[..., arch.nope:], lowp, "hqk,khd->qhd")
    return lin(pre + "attn/wo", o.reshape(s, h * arch.v))


def _linear(arch: Arch, root, layer, scales, lowp):
    """x @ Wᵀ of a per-layer linear ``name`` (``scales[name]``: (N, G))."""
    def lin(name, x):
        k = x.shape[-1]
        n = scales[name].shape[0]
        w = _dequant(arch, root, name, layer, scales[name], n, k)
        return _mm(x, w, lowp, "sk,nk->sn")
    return lin


def _gated(lin, x, pre: str):
    return lin(pre + "/down", jax.nn.silu(lin(pre + "/gate", x))
               * lin(pre + "/up", x))


def route(arch: Arch, root, layer, x, lowp):
    """(weights (S, E) of the experts each token runs, zero elsewhere)."""
    e = arch.experts
    router = jax.vmap(lambda i: weights.bias(
        root, "layers/moe/router/w", layer * e + i, arch.d))(jnp.arange(e))
    router = router * (arch.d ** -0.5 / 0.02)       # rows of norm about 1
    probs = jax.nn.softmax(_mm(x, router, lowp, "sd,ed->se"), axis=-1)
    top, idx = jax.lax.top_k(probs, arch.top_k)
    if arch.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    else:
        top = top * arch.routed_scale
    return jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def _moe(arch: Arch, root, layer, x, scales, lowp):
    gates = route(arch, root, layer, x, lowp)
    e = arch.experts

    def expert(acc, i):
        def lin(name, v):
            n, k = scales[name].shape[-2], v.shape[-1]
            w = _dequant(arch, root, name, layer * e + i, scales[name][i],
                         n, k)
            return _mm(v, w, lowp, "sk,nk->sn")
        return acc + gates[:, i, None] * _gated(lin, x, EXPERTS), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(e))
    if arch.shared:
        y = y + _gated(_linear(arch, root, layer, scales, lowp), x, SHARED)
    return y


def _block(arch: Arch, root, stack: str, layer, h, scales, lowp):
    """One pre-norm block of ``stack`` over one sequence h (S, d);
    ``scales`` holds this layer's scales of every linear of the stack."""
    pre = "dense/" if stack == "dense" else ""
    x = _rms(arch, root, "layers/" + pre + "ln1", layer, h, arch.d)
    h = h + _attention(arch, root, pre, layer, x, scales, lowp)
    x = _rms(arch, root, "layers/" + pre + "ln2", layer, h, arch.d)
    if stack == "dense":
        return h + _gated(_linear(arch, root, layer, scales, lowp), x,
                          "dense/mlp")
    return h + _moe(arch, root, layer, x, scales, lowp)


def hidden(arch: Arch, root, tokens, scales, lowp=None, remat=False):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,);
    ``scales``: {linear: (L, [E,] N, G)} (``base_scales``' keys)."""
    h = weights.embedding(root, arch.vocab, arch.d)[tokens]
    for stack in ("dense", "moe"):
        names = arch.linears(stack)
        sc = {k: scales[k] for k in names}

        def body(h, xs, stack=stack):
            layer, s = xs
            return _block(arch, root, stack, layer, h, s, lowp), None
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        h, _ = jax.lax.scan(body, h, (jnp.arange(arch.depth(stack)), sc))
    return _rms(arch, root, "final_norm", 0, h, arch.d)


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
    """Summed next-token cross entropy of one row; differentiable in
    ``scales``."""
    h = hidden(arch, root, tokens, scales, lowp, remat=True)
    lg = logits(arch, root, h, lowp)
    return (jax.nn.logsumexp(lg, -1)
            - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]).sum()


@partial(jax.jit, static_argnums=(0, 5))
def row_loss_and_grad(arch: Arch, root, scales, tokens, labels, lowp=None):
    return jax.value_and_grad(lambda s: nll_sum(arch, root, s, tokens,
                                                labels, lowp))(scales)


def adamw(grads: dict, state: dict, params: dict, opt: dict):
    """One step of AdamW with global-norm clipping and a constant rate, on
    host float64 (as the dense reference)."""
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
