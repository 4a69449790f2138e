"""Per-architecture smoke tests (reduced configs, CPU) + consistency checks.

Every assigned arch: one forward/train step asserting output shapes and no
NaNs — as required by the assignment.  Plus train↔decode agreement for the
recurrent families (the strongest correctness check a cache path can have).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ShapeConfig, SSMConfig, TuningConfig
from repro.models import mamba2, registry, xlstm


RNG = jax.random.PRNGKey(0)


def make_batch(cfg, api, seq=16, batch=2):
    shape = ShapeConfig("smoke", seq, batch, "train")
    specs = api.input_specs(shape)
    out = {}
    for k, v in specs.items():
        if v.dtype == jnp.int32:
            out[k] = jax.random.randint(RNG, v.shape, 0, cfg.vocab_size)
        else:
            out[k] = jax.random.normal(RNG, v.shape, v.dtype) * 0.1
    return out


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = configs.make_tiny(configs.get_config(arch)).replace(
        tuning=TuningConfig(mode="peqa"), quant=configs.QuantConfig(n_grid=2))
    api = registry.build(cfg)
    from repro.core import policies
    p, mask = policies.prepare(api.init(RNG), cfg, RNG)
    batch = make_batch(cfg, api)
    loss, grads = jax.value_and_grad(api.loss_fn, allow_int=True)(p, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    gleaves = [g for g, m in zip(jax.tree.leaves(grads), jax.tree.leaves(mask))
               if m and g.dtype != jax.dtypes.float0]
    assert all(np.isfinite(np.asarray(g)).all() for g in gleaves), \
        f"{arch}: NaN in grads"


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_decode_step(arch):
    cfg = configs.make_tiny(configs.get_config(arch)).replace(
        tuning=TuningConfig(mode="full"))
    api = registry.build(cfg)
    p = api.init(RNG)
    cache = api.init_cache(2, 16)
    toks = jnp.zeros((2, 1), jnp.int32)
    logits, new_cache = api.decode_step(p, cache, toks, jnp.int32(3))
    assert logits.shape == (2, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: NaN logits"
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen2-7b", "mixtral-8x7b"])
def test_dense_prefill_decode_matches_forward(arch):
    """prefill(t[:s]) + decode steps == teacher-forced forward logits."""
    # MoE routing is dropless, so the single-token decode dispatch routes
    # exactly as the full-sequence forward does
    cfg = configs.make_tiny(configs.get_config(arch)).replace(
        tuning=TuningConfig(mode="full"), swa_window=None)
    api = registry.build(cfg)
    p = api.init(RNG)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    from repro.models import transformer
    logits_fwd, _ = transformer.forward(p, toks, cfg)
    # prefill first 8 tokens, decode the rest
    cache = api.init_cache(B, S)
    lg, pcache = api.prefill(p, {"tokens": toks[:, :8]})
    cache = jax.tree.map(
        lambda full, part: jax.lax.dynamic_update_slice_in_dim(
            full, part.astype(full.dtype), 0, axis=2), cache, pcache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_fwd[:, 7]),
                               rtol=2e-4, atol=2e-4)
    for t in range(8, S):
        lg, cache = api.decode_step(p, cache, toks[:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_fwd[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_mamba2_chunked_equals_sequential():
    cfg = configs.make_tiny(configs.get_config("zamba2-7b"))
    cfg = cfg.replace(tuning=TuningConfig(mode="full"))
    p = mamba2.init(RNG, cfg)
    B, S = 2, 16
    u = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    y_train, st = mamba2.apply_train(p, u, cfg, return_state=True)
    state = mamba2.init_state(cfg, B, n_layers=1)
    s_l, c_l = state["ssm"][0], state["conv"][0]
    ys = []
    for t in range(S):
        yt, s_l, c_l = mamba2.apply_decode(p, u[:, t:t + 1], cfg, s_l, c_l)
        ys.append(yt)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_dec),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st["ssm"]), np.asarray(s_l),
                               rtol=1e-4, atol=1e-5)


def test_xlstm_decode_matches_forward():
    cfg = configs.make_tiny(configs.get_config("xlstm-125m")).replace(
        tuning=TuningConfig(mode="full"))
    api = registry.build(cfg)
    p = api.init(RNG)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    logits_fwd = xlstm.forward(p, toks, cfg)
    cache = api.init_cache(B, S)
    for t in range(S):
        lg, cache = api.decode_step(p, cache, toks[:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_fwd[:, t]), rtol=5e-4, atol=5e-4)


def test_zamba2_prefill_decode_consistency():
    cfg = configs.make_tiny(configs.get_config("zamba2-7b")).replace(
        tuning=TuningConfig(mode="full"))
    api = registry.build(cfg)
    p = api.init(RNG)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab_size)
    from repro.models import zamba2
    logits_fwd = zamba2.forward(p, toks, cfg)
    # full prefill's last logits == forward's last position
    lg, cache = api.prefill(p, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_fwd[:, -1]),
                               rtol=2e-4, atol=2e-4)
    # one decode step continues consistently (finite, right shape)
    lg2, _ = api.decode_step(p, cache, toks[:, :1], jnp.int32(S))
    assert np.isfinite(np.asarray(lg2)).all()


def test_whisper_shapes():
    cfg = configs.make_tiny(configs.get_config("whisper-medium")).replace(
        tuning=TuningConfig(mode="full"))
    api = registry.build(cfg)
    p = api.init(RNG)
    batch = make_batch(cfg, api, seq=16, batch=2)
    from repro.models import whisper
    logits = whisper.forward(p, batch["frames"], batch["tokens"], cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    lg, cache = api.prefill(p, batch)
    assert lg.shape == (2, cfg.vocab_size)
    lg2, _ = api.decode_step(p, cache, batch["tokens"][:, :1], jnp.int32(5))
    assert np.isfinite(np.asarray(lg2)).all()


def test_vlm_prefix_loss_alignment():
    cfg = configs.make_tiny(configs.get_config("llava-next-mistral-7b")
                            ).replace(tuning=TuningConfig(mode="full"))
    api = registry.build(cfg)
    p = api.init(RNG)
    batch = make_batch(cfg, api, seq=16, batch=2)
    assert batch["tokens"].shape[1] == 16 - cfg.n_img_tokens
    loss = api.loss_fn(p, batch)
    assert np.isfinite(float(loss))


def test_moe_capacity_drops_are_bounded():
    """Dropless routing (no assignment is dropped, so the bound is zero):
    the block's output is finite and the Switch aux loss of near-uniform
    routing is about 1."""
    from repro.models import moe
    cfg = configs.make_tiny(configs.get_config("mixtral-8x7b")).replace(
        tuning=TuningConfig(mode="full"))
    p = moe.init(RNG, cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.d_model)) * 0.5
    y, aux, _ = moe.apply(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.5  # Switch aux ≈ 1 for near-uniform routing


try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # image has no hypothesis; see fallback
    from _hypothesis_fallback import given, settings, st


@given(st.integers(0, 10_000), st.integers(1, 3), st.sampled_from([2, 4, 8]))
@settings(max_examples=10, deadline=None)
def test_ssd_chunked_equals_naive_recurrence(seed, heads, chunk):
    """Property: the chunked SSD scan == the naive per-step recurrence for
    random shapes/decays (the substrate under both Mamba2 and mLSTM)."""
    rng = np.random.default_rng(seed)
    b, s, hd, stt = 2, 8, 4, 3
    xh = jnp.asarray(rng.normal(size=(b, s, heads, hd)).astype(np.float32))
    bh = jnp.asarray(rng.normal(size=(b, s, heads, stt)).astype(np.float32))
    ch = jnp.asarray(rng.normal(size=(b, s, heads, stt)).astype(np.float32))
    la = jnp.asarray(-np.abs(rng.normal(size=(b, s, heads))).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, heads))).astype(np.float32))
    s0 = jnp.zeros((b, heads, hd, stt), jnp.float32)
    y, S_last = mamba2.ssd_chunked(xh, bh, ch, la, dt, s0, chunk)

    S = np.zeros((b, heads, hd, stt), np.float32)
    ys = []
    for t in range(s):
        a = np.exp(np.asarray(la[:, t]))[:, :, None, None]
        S = a * S + np.asarray(dt[:, t])[:, :, None, None] * \
            np.einsum("bhd,bhs->bhds", np.asarray(xh[:, t]), np.asarray(bh[:, t]))
        ys.append(np.einsum("bhds,bhs->bhd", S, np.asarray(ch[:, t])))
    y_ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S_last), S, rtol=2e-4, atol=2e-4)
