"""Continuous batching (paged KV slot pool) + decode-path regressions.

The acceptance bar for the continuous engine is TOKEN-FOR-TOKEN equality
with per-sequence lockstep decoding: admitting/evicting mid-loop, staggered
arrivals, mixed lengths and EOS cuts must never change what any single
request generates — only how many bubble slot-steps the pool pays (zero).

The regression half pins the decode-path bugfix sweep:
  * ``generate(cache_len=0)`` and too-short dense caches raise instead of
    letting XLA clamp the overflowing cache writes onto the last KV slot;
  * ``_grow_cache`` grows along the STRUCTURALLY inferred seq dim and
    refuses caches that differ on any other dim (the old first-mismatch
    pick updated the wrong axis);
  * the masked sampler pins inactive slots to the pad token;
  * a (B,) per-slot position vector decodes bit-identically to the scalar
    position it replaces;
  * cache rows past a slot's position are never read, on nibble and
    bit-plane backbones, through the plain and the resident pool.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import stale_rows_tokens

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.core import scale_bank as sb
from repro.dist import sampling
from repro.models import registry
from repro.serve import ServeConfig
from repro.train.serve import Engine, Request


def _make_engine(kv_cache_dtype="model", layout="nibble", bank=None):
    cfg = configs.paper_lm(n_layers=2, d_model=64, n_heads=2, d_ff=96,
                           vocab=128).replace(
        tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, n_grid=2, layout=layout),
        kv_cache_dtype=kv_cache_dtype)
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    if bank is not None:
        # task t0 is the backbone's scales, t1 a perturbation of them
        bank.add("t0", p)
        rs = np.random.default_rng(7)
        bank.tasks["t1"] = {k: (v * rs.uniform(0.8, 1.2, v.shape)
                                ).astype(v.dtype)
                            for k, v in bank.tasks["t0"].items()}
    return Engine(api, jax.tree.map(jnp.array, p), bank=bank)


@pytest.fixture(scope="module")
def engine():
    return _make_engine()


def _lockstep_ref(engine, req: Request) -> list:
    out = np.asarray(engine.generate(jnp.asarray(req.tokens)[None],
                                     n_new=req.n_new))
    return list(out[0, len(req.tokens):])


def test_continuous_matches_lockstep_token_for_token(engine):
    rs = np.random.default_rng(3)
    shapes = [(6, 4, 0), (5, 9, 0), (7, 3, 1), (6, 6, 2), (4, 12, 3),
              (8, 2, 5), (6, 5, 9)]
    reqs = [Request(tokens=rs.integers(0, 128, size=s).astype(np.int32),
                    n_new=n, arrival_step=a) for s, n, a in shapes]
    rep = engine.serve(reqs, ServeConfig(n_slots=2))  # 7 reqs through 2 slots
    assert rep.bubble_slot_steps == 0
    assert rep.decoded == sum(n for _, n, _ in shapes)
    # mid-loop admission actually happened: the pool is smaller than the
    # request count, and the step count beats decoding requests one by one
    assert rep.steps < sum(n - 1 for _, n, _ in shapes)
    for i, req in enumerate(reqs):
        assert rep.tokens[i] == _lockstep_ref(engine, req), f"req {i}"


def test_continuous_int8_kv_cache():
    eng = _make_engine(kv_cache_dtype="int8")
    reqs = [Request(tokens=np.arange(5, dtype=np.int32) * (i + 2) % 128,
                    n_new=4 + 3 * i) for i in range(3)]
    rep = eng.serve(reqs, ServeConfig(n_slots=2))
    for i, req in enumerate(reqs):
        assert rep.tokens[i] == _lockstep_ref(eng, req), f"req {i}"


def test_eos_eviction_mid_loop(engine):
    req = Request(tokens=np.arange(6, dtype=np.int32), n_new=10)
    ref = _lockstep_ref(engine, req)
    # first token value whose first occurrence is mid-stream: generation
    # must stop right there when it is declared EOS
    j = next((j for j in range(1, len(ref)) if ref[j] not in ref[:j]), None)
    if j is None:
        pytest.skip("reference stream has no unique mid-stream token")
    rep = engine.serve([Request(tokens=req.tokens, n_new=10,
                                eos_id=int(ref[j]))], ServeConfig(n_slots=2))
    assert rep.tokens[0] == ref[:j + 1]
    # EOS on the PREFILL token: finishes at admit, zero decode steps
    rep0 = engine.serve([Request(tokens=req.tokens, n_new=10,
                                 eos_id=int(ref[0]))], ServeConfig(n_slots=2))
    assert rep0.tokens[0] == ref[:1] and rep0.steps == 0


def test_vector_pos_decode_matches_scalar(engine):
    api = engine.api
    toks = jnp.tile(jnp.arange(6, dtype=jnp.int32)[None], (2, 1))
    logits, cache = engine._prefill(engine.params, {"tokens": toks})
    cache = engine._grow_cache(cache, 2, 16, 6)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    l_s, c_s = api.decode_step(engine.params, cache, tok, jnp.int32(6))
    l_v, c_v = jax.jit(api.decode_step)(
        engine.params, cache, tok, jnp.full((2,), 6, jnp.int32))
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_sampler_pins_inactive_slots():
    lg = jnp.asarray(np.random.default_rng(0).normal(size=(3, 16)),
                     jnp.float32)
    sample = sampling.shard_argmax_masked(None, 3)
    got = np.asarray(sample(lg, jnp.asarray([True, False, True])))
    want = np.argmax(np.asarray(lg), axis=-1)
    assert got[0] == want[0] and got[2] == want[2]
    assert got[1] == 0


# ------------------------------------------------------------- regressions

def test_generate_cache_len_zero_raises(engine):
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="must be positive"):
        engine.generate(toks, n_new=4, cache_len=0)


def test_generate_cache_len_too_short_raises(engine):
    """A dense cache shorter than prompt+n_new-1 used to be accepted: XLA
    clamps the out-of-range dynamic_update_slice writes and every
    overflowing token silently overwrites the LAST KV slot."""
    toks = jnp.zeros((1, 6), jnp.int32)
    with pytest.raises(ValueError, match="clamp"):
        engine.generate(toks, n_new=8, cache_len=9)
    # exactly-fitting cache is fine — the final sampled token's KV is
    # never written, so prompt+n_new-1 slots suffice
    ref = np.asarray(engine.generate(toks, n_new=3))
    tight = np.asarray(engine.generate(toks, n_new=3, cache_len=8))
    np.testing.assert_array_equal(ref, tight)


def test_sliding_window_continuous_matches_lockstep():
    """swa_window <= the structural probe length used to blind the seq-dim
    inference (capacity clamps to the window at both probe lengths), making
    every generate/admit raise; the probe must straddle the clamp."""
    cfg = configs.paper_lm(n_layers=2, d_model=64, n_heads=2, d_ff=96,
                           vocab=128).replace(
        tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, n_grid=2), swa_window=6)
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    eng = Engine(api, jax.tree.map(jnp.array, p))
    reqs = [Request(tokens=np.arange(4, dtype=np.int32) * (i + 1) % 128,
                    n_new=3 + 2 * i) for i in range(3)]
    rep = eng.serve(reqs, ServeConfig(n_slots=2))
    for i, req in enumerate(reqs):
        assert rep.tokens[i] == _lockstep_ref(eng, req), f"req {i}"


def test_grow_cache_two_dims_differ_raises(engine):
    """The old ``place`` picked the FIRST mismatched dim as the seq axis;
    a batch-padded prompt cache (batch AND seq differ) would silently
    update the batch dim.  Now: structural seq-dim inference + a hard
    error on any non-seq mismatch."""
    toks = jnp.tile(jnp.arange(5, dtype=jnp.int32)[None], (2, 1))
    _, cache = engine._prefill(engine.params, {"tokens": toks})
    with pytest.raises(ValueError, match="seq dim"):
        engine._grow_cache(cache, 4, 16, 5)      # pool batch 4 != prompt 2
    grown = engine._grow_cache(cache, 2, 16, 5)  # seq-only growth is fine
    for leaf, src in zip(jax.tree.leaves(grown), jax.tree.leaves(cache)):
        assert leaf.shape[2] == 16
        np.testing.assert_array_equal(np.asarray(leaf)[:, :, :5],
                                      np.asarray(src))


def test_admit_validation(engine):
    pool = engine.open_pool(2, 12)
    with pytest.raises(ValueError, match="cache slots"):
        engine.admit(pool, Request(tokens=np.arange(6, dtype=np.int32),
                                   n_new=10))
    engine.admit(pool, Request(tokens=np.arange(4, dtype=np.int32), n_new=8))
    engine.admit(pool, Request(tokens=np.arange(4, dtype=np.int32), n_new=8))
    with pytest.raises(RuntimeError, match="no free slot"):
        engine.admit(pool, Request(tokens=np.arange(4, dtype=np.int32),
                                   n_new=8))


def test_pool_rejects_capless_apis():
    """Slot admission is a protocol now: the pool keys on the registry's
    ``FamilyCaps`` record, so a hand-rolled API without one must be refused
    loudly instead of tracing garbage (every registry family has a record —
    SSM/recurrent included, served as pure per-row slot writes)."""
    fake = types.SimpleNamespace(
        cfg=types.SimpleNamespace(family="ssm", vocab_size=8),
        prefill=lambda *a: None, decode_step=lambda *a: None,
        init_cache=lambda b, s: {})
    eng = Engine(fake, {})
    with pytest.raises(NotImplementedError, match="capability record"):
        eng.open_pool(2, 8)


@pytest.mark.parametrize("pool", ["plain", "resident"])
@pytest.mark.parametrize("layout", ["nibble", "plane"])
def test_stale_rows_past_pos_never_read(layout, pool):
    """Rows an evicted request left past the new occupant's position, and
    every other row at or past a slot's ``pos``, are dead state: poisoning
    them changes no token (``conftest.stale_rows_tokens``)."""
    if pool == "plain":
        eng, tasks = _make_engine(layout=layout), (None, None)
    else:
        eng = _make_engine(layout=layout, bank=sb.ScaleBank())
        eng.resident = sb.ResidentStack(eng.bank, eng.params, 2)
        tasks = ("t0", "t1")
    slots = eng.open_pool(2, 24)
    slots.slotted = pool == "resident"
    clean, poisoned = stale_rows_tokens(eng, slots, tasks)
    assert clean == poisoned
