"""Mamba2 (SSD) block — chunked parallel scan for training/prefill, O(1)
recurrent state for decode.  This is the sub-quadratic substrate for
zamba2-7b and the reason that arch runs the ``long_500k`` shape.

Recurrence per head h (A scalar per head, Mamba2 simplification):

    S_t = exp(A_h · dt_t) · S_{t-1} + dt_t · x_t ⊗ B_t          (d_head, d_state)
    y_t = S_t · C_t + D_h · x_t

Training uses the SSD chunked form in LOG space (decays multiply → cumsum of
dt·A): within a chunk of length c the output is an attention-like quadratic
form  (C Bᵀ ⊙ decay-mask) X  (cost c²·(d_state + d_head) per head), across
chunks the state is carried by a lax.scan.  This is the TPU-friendly
adaptation: the quadratic intra-chunk term is MXU work, the scan carries a
small (heads, d_head, d_state) state.

Projections are SPLIT (zproj/xproj/bproj/cproj/dtproj) instead of the fused
in_proj so each shards cleanly: z/x/dt column-parallel over 'model' (heads
sharded), B/C replicated (they are tiny and shared across heads per group),
out_proj row-parallel — exactly one all-reduce per block (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, linear


def _dims(cfg: ModelConfig):
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    n_heads = d_inner // ssm.head_dim
    return d_inner, n_heads


def init(rng, cfg: ModelConfig) -> dict:
    ssm = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads = _dims(cfg)
    ks = jax.random.split(rng, 7)
    p = {
        "zproj": linear.init(ks[0], d, d_inner),
        "xproj": linear.init(ks[1], d, d_inner),
        "bproj": linear.init(ks[2], d, ssm.n_groups * ssm.d_state),
        "cproj": linear.init(ks[3], d, ssm.n_groups * ssm.d_state),
        "dtproj": linear.init(ks[4], d, n_heads),
        "conv": {
            "w": (jax.random.normal(ks[5], (d_inner, ssm.d_conv)) *
                  ssm.d_conv ** -0.5).astype(jnp.float32),
            "b": jnp.zeros((d_inner,), jnp.float32),
        },
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "ssm_D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.full((n_heads,), -2.0, jnp.float32),  # softplus ≈ 0.12
        "gnorm": {"g": jnp.ones((d_inner,), jnp.float32)},
        "out_proj": linear.init(ks[6], d_inner, d),
    }
    return p


def init_state(cfg: ModelConfig, batch: int, n_layers: Optional[int] = None):
    ssm = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    dtype = jnp.float32  # SSM state carried in f32
    return {
        "ssm": jnp.zeros((n_layers, batch, n_heads, ssm.head_dim, ssm.d_state), dtype),
        "conv": jnp.zeros((n_layers, batch, ssm.d_conv - 1, d_inner), dtype),
    }


def _conv1d_causal(x, w, b):
    """Depthwise causal conv. x (B,S,C), w (C,W), b (C)."""
    wdt = w.astype(x.dtype)
    width = w.shape[-1]
    xpad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xpad[:, i:i + x.shape[1]] * wdt[:, i] for i in range(width))
    return out + b.astype(x.dtype)


def _gates(p, u, cfg: ModelConfig):
    spec = cfg.quant.spec()
    ssm = cfg.ssm
    b, s, _ = u.shape
    d_inner, n_heads = _dims(cfg)
    z = linear.apply(p["zproj"], u, spec)
    x = linear.apply(p["xproj"], u, spec)
    bb = linear.apply(p["bproj"], u, spec).reshape(b, s, ssm.n_groups, ssm.d_state)
    cc = linear.apply(p["cproj"], u, spec).reshape(b, s, ssm.n_groups, ssm.d_state)
    dt_raw = linear.apply(p["dtproj"], u, spec)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    return z, x, bb, cc, dt


def _expand_groups(t, n_heads, n_groups):
    """(B,S,G,N) → (B,S,H,N) by repeating each group across its heads."""
    return jnp.repeat(t, n_heads // n_groups, axis=2)


def ssd_chunked(xh, bh, ch_, la, dt, s0, chunk: int):
    """Chunked linear-recurrence scan (shared by Mamba2 and mLSTM).

    Recurrence  S_t = exp(la_t)·S_{t-1} + dt_t · x_t ⊗ B_t,   y_t = S_t·C_t.
    xh (B,S,H,hd), bh/ch_ (B,S,H,st), la/dt (B,S,H), s0 (B,H,hd,st).
    Returns (y (B,S,H,hd), S_last).
    """
    bsz, s, n_heads, hd = xh.shape
    st = bh.shape[-1]
    ch = min(chunk, s)
    assert s % ch == 0, f"seq {s} % chunk {ch} != 0"
    n_chunks = s // ch

    def to_chunks(t):
        return t.reshape(bsz, n_chunks, ch, *t.shape[2:])

    xh_c, bh_c, ch_c, la_c, dt_c = map(to_chunks, (xh, bh, ch_, la, dt))

    def chunk_body(carry, inp):
        S_prev = carry                                           # (B,H,hd,st)
        xc, bc, cc_, lac, dtc = inp                              # (B,ch,H,…)
        cum = jnp.cumsum(lac, axis=1)                            # (B,ch,H)
        # inter-chunk: y_prev_t = C_t · (exp(cum_t) S_prev)
        y_inter = jnp.einsum("bths,bhds,bth->bthd", cc_, S_prev,
                             jnp.exp(cum))
        # intra-chunk quadratic form.  The decay exponent is ≤ 0 exactly on
        # the causal (j ≤ i) region; clamp BEFORE exp so the masked j > i
        # entries can't overflow to inf (0·inf in the backward of `where`
        # would poison every gradient upstream).
        scores = jnp.einsum("bihs,bjhs->bhij", cc_, bc)          # (B,H,ch,ch)
        dexp = (cum.transpose(0, 2, 1)[..., :, None]
                - cum.transpose(0, 2, 1)[..., None, :])          # (B,H,ch_i,ch_j)
        decay = jnp.exp(jnp.minimum(dexp, 0.0))
        mask = jnp.tril(jnp.ones((ch, ch), bool))
        g = jnp.where(mask, scores * decay, 0.0)
        g = g * dtc.transpose(0, 2, 1)[:, :, None, :]            # · dt_j
        y_intra = jnp.einsum("bhij,bjhd->bihd", g, xc)
        # state update
        wgt = jnp.exp(cum[:, -1:, :] - cum) * dtc                # (B,ch,H)
        S_new = (jnp.exp(cum[:, -1])[..., None, None] * S_prev
                 + jnp.einsum("bth,bthd,bths->bhds", wgt, xc, bc))
        return S_new, y_inter + y_intra

    def swap(t):
        return jnp.swapaxes(t, 0, 1)                             # chunks leading

    S_last, y = jax.lax.scan(
        chunk_body, s0,
        tuple(map(swap, (xh_c, bh_c, ch_c, la_c, dt_c))))
    return swap(y).reshape(bsz, s, n_heads, hd), S_last


def apply_train(p: dict, u: jax.Array, cfg: ModelConfig,
                state: Optional[dict] = None, return_state: bool = False):
    """Full-sequence SSD. u: (B, S, d_model) → (B, S, d_model)."""
    ssm = cfg.ssm
    bsz, s, _ = u.shape
    d_inner, n_heads = _dims(cfg)
    hd, st = ssm.head_dim, ssm.d_state

    z, x_raw, bb, cc, dt = _gates(p, u, cfg)
    x = _conv1d_causal(x_raw, p["conv"]["w"], p["conv"]["b"])
    x = jax.nn.silu(x)
    xh = x.reshape(bsz, s, n_heads, hd).astype(jnp.float32)
    bh = _expand_groups(bb, n_heads, ssm.n_groups).astype(jnp.float32)
    chd = _expand_groups(cc, n_heads, ssm.n_groups).astype(jnp.float32)
    a = -jnp.exp(p["A_log"])                                     # (H,) < 0
    la = dt * a                                                  # (B,S,H) log-decay ≤ 0

    s0 = jnp.zeros((bsz, n_heads, hd, st), jnp.float32) if state is None \
        else state
    y, S_last = ssd_chunked(xh, bh, chd, la, dt, s0, ssm.chunk)
    y = y + xh * p["ssm_D"][None, None, :, None]
    y = y.reshape(bsz, s, d_inner).astype(u.dtype)
    y = y * jax.nn.silu(z)
    y = common.norm_apply(p["gnorm"], y, cfg)
    out = linear.apply(p["out_proj"], y, cfg.quant.spec(), row_parallel=True)
    if return_state:
        # decode's rolling conv window holds PRE-conv xproj outputs
        tail = ssm.d_conv - 1
        conv_tail = x_raw[:, -tail:].astype(jnp.float32) if s >= tail \
            else jnp.pad(x_raw, ((0, 0), (tail - s, 0), (0, 0))).astype(jnp.float32)
        return out, {"ssm": S_last, "conv": conv_tail}
    return out


def apply_decode(p: dict, u: jax.Array, cfg: ModelConfig,
                 ssm_state: jax.Array, conv_state: jax.Array):
    """One-token step. u (B, 1, d); ssm_state (B,H,hd,st); conv_state
    (B, W-1, d_inner). Returns (out (B,1,d), ssm_state, conv_state)."""
    ssm = cfg.ssm
    bsz = u.shape[0]
    d_inner, n_heads = _dims(cfg)
    hd, st = ssm.head_dim, ssm.d_state

    z, x, bb, cc, dt = _gates(p, u, cfg)                        # S = 1
    # conv over rolling window
    xw = jnp.concatenate([conv_state.astype(x.dtype), x.astype(x.dtype)], axis=1)
    w = p["conv"]["w"].astype(x.dtype)
    xc = jnp.einsum("bwc,cw->bc", xw, w) + p["conv"]["b"].astype(x.dtype)
    xc = jax.nn.silu(xc)                                        # (B, d_inner)
    new_conv = xw[:, 1:].astype(jnp.float32)

    xh = xc.reshape(bsz, n_heads, hd).astype(jnp.float32)
    bh = _expand_groups(bb, n_heads, ssm.n_groups)[:, 0].astype(jnp.float32)
    chd = _expand_groups(cc, n_heads, ssm.n_groups)[:, 0].astype(jnp.float32)
    dt0 = dt[:, 0]                                              # (B,H)
    a = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt0 * a)                                    # (B,H)
    S = (decay[..., None, None] * ssm_state
         + jnp.einsum("bh,bhd,bhs->bhds", dt0, xh, bh))
    y = jnp.einsum("bhds,bhs->bhd", S, chd) + xh * p["ssm_D"][None, :, None]
    y = y.reshape(bsz, 1, d_inner).astype(u.dtype)
    y = y * jax.nn.silu(z)
    y = common.norm_apply(p["gnorm"], y, cfg)
    out = linear.apply(p["out_proj"], y, cfg.quant.spec(), row_parallel=True)
    return out, S, new_conv
