"""xLSTM: mLSTM (matrix-memory, parallel-chunkwise) + sLSTM (scalar-memory,
recurrent) blocks — the pure-recurrent assigned arch (xlstm-125m).

Block pattern: every ``slstm_every``-th layer is an sLSTM, the rest are
mLSTM.  Layers are grouped [sLSTM, mLSTM×(slstm_every−1)] and scanned
(nested, zamba2-style) so the HLO is O(1) in depth.

mLSTM cell (per head, state C ∈ R^{hd×hd}, normalizer n ∈ R^{hd}):

    f_t = σ(f̃_t)   i_t = exp(clip(ĩ_t, ±CLIP))
    C_t = f_t C_{t-1} + i_t v_t kᵀ_t        n_t = f_t n_{t-1} + i_t k_t
    y_t = (C_t q_t) / max(|n_t · q_t|, 1)

Training uses the SAME chunked machinery as Mamba2 (`mamba2.ssd_chunked`)
with the mapping x→[v;1], B→k, C→q, dt→i, log-decay→logσ(f̃): the augmented
row carries the normalizer recurrence for free.  The hard clip on the exp
input gate replaces xLSTM's running-max stabilizer (per-chunk floats stay
bounded; documented simplification, DESIGN.md §6).

sLSTM keeps the paper's exact stabilized recurrence (running max m_t) with
block-diagonal per-head recurrent matrices — a genuine sequential
lax.scan over time (O(1)-state decode is what makes this arch run
``long_500k``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, linear, mamba2

ICLIP = 8.0  # input-gate exp clip


def _dims(cfg: ModelConfig):
    d_inner = 2 * cfg.d_model      # mLSTM proj factor 2
    hd = d_inner // cfg.n_heads
    return d_inner, hd


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner, _ = _dims(cfg)
    ks = jax.random.split(rng, 7)
    return {
        "ln": common.norm_init(cfg),
        "wq": linear.init(ks[0], d, d_inner),
        "wk": linear.init(ks[1], d, d_inner),
        "wv": linear.init(ks[2], d, d_inner),
        "gate": linear.init(ks[3], d, d_inner),          # output gate (column)
        "gi": linear.init(ks[4], d, cfg.n_heads),        # scalar gates: replicated
        "gf": linear.init(ks[5], d, cfg.n_heads),
        "down": linear.init(ks[6], d_inner, d),
    }


def _mlstm_gates(p, u, cfg: ModelConfig):
    spec = cfg.quant.spec()
    b, s, _ = u.shape
    d_inner, hd = _dims(cfg)
    h = cfg.n_heads

    def proj(name, dim, dh):
        return linear.apply(p[name], u, spec).reshape(b, s, dim, dh)

    q = proj("wq", h, hd).astype(jnp.float32) * hd ** -0.5
    k = proj("wk", h, hd).astype(jnp.float32) * hd ** -0.5
    v = proj("wv", h, hd).astype(jnp.float32)
    og = jax.nn.sigmoid(linear.apply(p["gate"], u, spec)
                        .astype(jnp.float32))
    i_raw = linear.apply(p["gi"], u, spec).astype(jnp.float32)
    f_raw = linear.apply(p["gf"], u, spec).astype(jnp.float32)
    ig = jnp.exp(jnp.clip(i_raw, -ICLIP, ICLIP))                  # (B,S,H)
    logf = jax.nn.log_sigmoid(f_raw)                              # (B,S,H)
    return q, k, v, og, ig, logf


def mlstm_apply_train(p: dict, u_res: jax.Array, cfg: ModelConfig,
                      state: Optional[jax.Array] = None,
                      return_state: bool = False):
    """u_res: (B,S,d) residual-stream input.  state: (B,H,hd+1,hd)."""
    b, s, _ = u_res.shape
    d_inner, hd = _dims(cfg)
    u = common.norm_apply(p["ln"], u_res, cfg)
    q, k, v, og, ig, logf = _mlstm_gates(p, u, cfg)
    ones = jnp.ones((*v.shape[:-1], 1), v.dtype)
    v_aug = jnp.concatenate([v, ones], axis=-1)                   # (B,S,H,hd+1)
    s0 = state if state is not None else \
        jnp.zeros((b, cfg.n_heads, hd + 1, hd), jnp.float32)
    y_aug, S_last = mamba2.ssd_chunked(v_aug, k, q, logf, ig, s0,
                                       cfg.ssm.chunk if cfg.ssm else 128)
    y, nq = y_aug[..., :hd], y_aug[..., hd]
    y = y / jnp.maximum(jnp.abs(nq), 1.0)[..., None]
    y = (y.reshape(b, s, d_inner) * og).astype(u_res.dtype)
    out = linear.apply(p["down"], y, cfg.quant.spec(), row_parallel=True)
    if return_state:
        return out, S_last
    return out


def mlstm_apply_decode(p: dict, u_res: jax.Array, cfg: ModelConfig,
                       state: jax.Array):
    """One step. u_res (B,1,d); state (B,H,hd+1,hd)."""
    b = u_res.shape[0]
    d_inner, hd = _dims(cfg)
    u = common.norm_apply(p["ln"], u_res, cfg)
    q, k, v, og, ig, logf = _mlstm_gates(p, u, cfg)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                            # (B,H,hd)
    ig, logf, og = ig[:, 0], logf[:, 0], og[:, 0]
    f = jnp.exp(logf)[..., None, None]
    v_aug = jnp.concatenate([v, jnp.ones((b, cfg.n_heads, 1), v.dtype)], -1)
    S = f * state + ig[..., None, None] * jnp.einsum("bhv,bhk->bhvk", v_aug, k)
    y_aug = jnp.einsum("bhvk,bhk->bhv", S, q)
    y, nq = y_aug[..., :hd], y_aug[..., hd]
    y = y / jnp.maximum(jnp.abs(nq), 1.0)[..., None]
    y = y.reshape(b, 1, d_inner) * og[:, None]
    out = linear.apply(p["down"], y.astype(u_res.dtype), cfg.quant.spec(),
                       row_parallel=True)
    return out, S


# ---------------------------------------------------------------------------
# sLSTM (exact stabilized recurrence, block-diagonal recurrent weights)
# ---------------------------------------------------------------------------

def slstm_init(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    ks = jax.random.split(rng, 6)
    r = (jax.random.normal(ks[4], (4, h, hd, hd)) * hd ** -0.5).astype(jnp.float32)
    return {
        "ln": common.norm_init(cfg),
        "sw": linear.init(ks[0], d, 4 * d),   # z,i,f,o pre-activations (replicated)
        "sr": {"r": r},                        # recurrent block-diag (z,i,f,o)
        "sb": {"b": jnp.zeros((4, d), jnp.float32)},
        "down": linear.init(ks[5], d, d),
    }


def slstm_apply_train(p: dict, u_res: jax.Array, cfg: ModelConfig,
                      state: Optional[dict] = None,
                      return_state: bool = False):
    b, s, d = u_res.shape
    h = cfg.n_heads
    hd = d // h
    u = common.norm_apply(p["ln"], u_res, cfg)
    wx = linear.apply(p["sw"], u, cfg.quant.spec())
    wx = wx.astype(jnp.float32).reshape(b, s, 4, h, hd) + \
        p["sb"]["b"].reshape(4, h, hd)
    r = p["sr"]["r"]

    if state is None:
        state = slstm_zero_state(cfg, b)

    def step(carry, wx_t):
        c, n, m, hprev = carry
        rec = jnp.einsum("ghij,bhj->bghi", r, hprev)              # (B,4,H,hd)
        pre = wx_t + rec
        zt = jnp.tanh(pre[:, 0])
        it_ = pre[:, 1]
        ft_ = jax.nn.log_sigmoid(pre[:, 2])
        ot = jax.nn.sigmoid(pre[:, 3])
        m_new = jnp.maximum(ft_ + m, it_)
        i_s = jnp.exp(it_ - m_new)
        f_s = jnp.exp(ft_ + m - m_new)
        c = f_s * c + i_s * zt
        n = f_s * n + i_s
        hnew = ot * c / jnp.maximum(jnp.abs(n), 1e-6)
        return (c, n, m_new, hnew), hnew

    wx_t = jnp.swapaxes(wx, 0, 1)                                 # (S,B,4,H,hd)
    carry, ys = jax.lax.scan(step, state, wx_t)
    y = jnp.swapaxes(ys, 0, 1).reshape(b, s, d).astype(u_res.dtype)
    out = linear.apply(p["down"], y, cfg.quant.spec(), row_parallel=True)
    if return_state:
        return out, carry
    return out


def slstm_zero_state(cfg: ModelConfig, batch: int):
    h = cfg.n_heads
    hd = cfg.d_model // h
    z = jnp.zeros((batch, h, hd), jnp.float32)
    return (z, z, jnp.full((batch, h, hd), -1e9, jnp.float32), z)


def slstm_apply_decode(p: dict, u_res: jax.Array, cfg: ModelConfig, state):
    out, carry = slstm_apply_train(p, u_res, cfg, state=state, return_state=True)
    return out, carry


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _layout(cfg: ModelConfig):
    every = cfg.slstm_every or (cfg.n_layers + 1)
    n_groups = cfg.n_layers // every
    n_m = every - 1
    tail = cfg.n_layers - n_groups * every
    assert tail == 0, "xlstm: n_layers must divide by slstm_every"
    return every, n_groups, n_m


def init(rng, cfg: ModelConfig) -> dict:
    every, n_groups, n_m = _layout(cfg)
    ks = jax.random.split(rng, 5)

    def stack(initf, r, n):
        return jax.vmap(lambda rr: initf(rr, cfg))(jax.random.split(r, n))

    slstm = stack(slstm_init, ks[0], n_groups)
    mlstm = stack(mlstm_init, ks[1], n_groups * n_m)
    mlstm = jax.tree.map(lambda l: l.reshape(n_groups, n_m, *l.shape[1:]), mlstm)
    params = {
        "embed": common.embed_init(ks[2], cfg),
        "slstm": slstm,
        "mlstm": mlstm,
        "final_norm": common.norm_init(cfg),
    }
    params.update(common.head_init(ks[3], cfg))
    return params


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig):
    hcur = common.embed_apply(params["embed"], tokens, cfg)

    def group_body(h, xs):
        sl_p, ml_p = xs
        h = h + slstm_apply_train(sl_p, h, cfg)

        def m_body(hh, layer_p):
            return hh + mlstm_apply_train(layer_p, hh, cfg), None
        body = m_body
        if cfg.remat in ("block", "full"):
            body = jax.checkpoint(m_body, prevent_cse=False)
        h, _ = jax.lax.scan(body, h, ml_p)
        return h, None

    h, _ = jax.lax.scan(group_body, hcur, (params["slstm"], params["mlstm"]))
    h = common.norm_apply(params["final_norm"], h, cfg)
    return common.head_apply(params, params["embed"], h, cfg)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch["tokens"], cfg)
    return common.cross_entropy(logits, batch["labels"], batch.get("mask"))


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    every, n_groups, n_m = _layout(cfg)
    d_inner, hd = _dims(cfg)
    h = cfg.n_heads
    shd = cfg.d_model // h
    def z():
        # one buffer PER leaf: the serving engine donates the cache into
        # its jitted admit/decode steps, and donation rejects aliased args
        return jnp.zeros((n_groups, batch, h, shd), jnp.float32)
    return {
        "s_c": z(), "s_n": z(),
        "s_m": jnp.full((n_groups, batch, h, shd), -1e9, jnp.float32),
        "s_h": z(),
        "m_S": jnp.zeros((n_groups, n_m, batch, h, hd + 1, hd), jnp.float32),
    }


def decode_step(params: dict, cache: dict, tokens: jax.Array, pos: jax.Array,
                cfg: ModelConfig):
    del pos  # recurrent: position-free
    h = common.embed_apply(params["embed"], tokens, cfg)

    def group_body(hh, xs):
        sl_p, ml_p, sc, sn, sm, sh, mS = xs
        out, (sc, sn, sm, sh) = slstm_apply_decode(
            sl_p, hh, cfg, (sc, sn, sm, sh))
        hh = hh + out

        def m_body(hhh, inner):
            layer_p, S = inner
            out, S = mlstm_apply_decode(layer_p, hhh, cfg, S)
            return hhh + out, S

        hh, mS = jax.lax.scan(m_body, hh, (ml_p, mS))
        return hh, (sc, sn, sm, sh, mS)

    h, (sc, sn, sm, sh, mS) = jax.lax.scan(
        group_body, h,
        (params["slstm"], params["mlstm"], cache["s_c"], cache["s_n"],
         cache["s_m"], cache["s_h"], cache["m_S"]))
    new_cache = {"s_c": sc, "s_n": sn, "s_m": sm, "s_h": sh, "m_S": mS}
    h = common.norm_apply(params["final_norm"], h, cfg)
    logits = common.head_apply(params, params["embed"], h, cfg)
    return logits[:, 0], new_cache


def prefill(params: dict, tokens: jax.Array, cfg: ModelConfig):
    """Prefill = forward pass that also materializes recurrent states."""
    b = tokens.shape[0]
    h = common.embed_apply(params["embed"], tokens, cfg)

    def group_body(hh, xs):
        sl_p, ml_p = xs
        out, sstate = slstm_apply_train(sl_p, hh, cfg, return_state=True)
        hh = hh + out

        def m_body(hhh, layer_p):
            out, S = mlstm_apply_train(layer_p, hhh, cfg, return_state=True)
            return hhh + out, S

        hh, mS = jax.lax.scan(m_body, hh, ml_p)
        return hh, (*sstate, mS)

    h, (sc, sn, sm, sh, mS) = jax.lax.scan(
        group_body, h, (params["slstm"], params["mlstm"]))
    cache = {"s_c": sc, "s_n": sn, "s_m": sm, "s_h": sh, "m_S": mS}
    h = common.norm_apply(params["final_norm"], h, cfg)
    logits = common.head_apply(params, params["embed"], h[:, -1:], cfg)
    return logits[:, 0], cache