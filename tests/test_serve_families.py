"""Family-agnostic slot protocol: every served family through ONE pool.

The continuous-batching engine keys on the registry's ``FamilyCaps``
record and the structurally inferred cache dims — not on the family name —
so encdec (paged cross-KV prefix state), vlm (image-embedding prefix
occupying decoder positions), and SSM/hybrid (position-free recurrent rows)
all admit through the same ``SlotPool``.  The acceptance bar per family is
the dense bar: token-for-token equality with per-request lockstep
``generate`` over staggered mixed-length traffic, zero bubble slot-steps.

The oracle half pins the structural machinery the protocol rests on:
``cache_seq_dims`` marks position-free leaves with -1 (whisper's cross-KV
vs its self-KV, every xlstm leaf), ``_grow_cache`` refuses to grow them,
and prefix validation rejects family/prefix mismatches loudly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.dist import sharding as shard_rules
from repro.models import registry
from repro.serve import ServeConfig
from repro.train.serve import Engine, Request

# one arch per non-dense served family; prompt lengths for the recurrent
# families are multiples of the tiny SSMConfig.chunk (chunked-SSD prefill
# asserts divisibility — a lockstep constraint, not a pool one)
_KV_SHAPES = ((6, 4, 0), (5, 9, 0), (7, 3, 1), (6, 6, 2), (4, 12, 3))
_CHUNKED_SHAPES = ((8, 4, 0), (16, 7, 0), (8, 3, 1), (24, 5, 3), (16, 6, 6))
FAMILY_ARCHS = ("whisper-medium", "llava-next-mistral-7b", "xlstm-125m",
                "zamba2-7b")


def _make_engine(arch):
    cfg = configs.make_tiny(configs.get_config(arch)).replace(
        tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    return Engine(api, jax.tree.map(jnp.array, p)), cfg


def _requests(cfg, seed=11):
    rng = np.random.default_rng(seed)
    shapes = _CHUNKED_SHAPES if cfg.family in ("ssm", "hybrid") \
        else _KV_SHAPES
    reqs = []
    for s, n_new, arrival in shapes:
        prefix = None
        if cfg.family == "encdec":
            prefix = rng.normal(size=(cfg.enc_frames, cfg.d_model)
                                ).astype(np.float32)
        elif cfg.family == "vlm":
            prefix = rng.normal(size=(cfg.n_img_tokens, cfg.d_model)
                                ).astype(np.float32)
        reqs.append(Request(
            tokens=rng.integers(0, cfg.vocab_size, size=s).astype(np.int32),
            n_new=n_new, arrival_step=arrival, prefix=prefix))
    return reqs


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_continuous_matches_lockstep(arch):
    eng, cfg = _make_engine(arch)
    reqs = _requests(cfg)
    rep = eng.serve(reqs, ServeConfig(n_slots=2))
    assert rep.bubble_slot_steps == 0
    assert rep.decoded == sum(r.n_new for r in reqs)
    for i, r in enumerate(reqs):
        pref = None if r.prefix is None else jnp.asarray(r.prefix)[None]
        ref = np.asarray(eng.generate(jnp.asarray(r.tokens)[None],
                                      n_new=r.n_new, prefix=pref))
        assert rep.tokens[i] == list(ref[0, len(r.tokens):]), f"req {i}"


def test_family_caps_registry_complete():
    """Every registry family ships a capability record whose fields agree
    with the decode machinery it actually exposes."""
    for arch in configs.ARCHS:
        cfg = configs.make_tiny(configs.get_config(arch)).replace(
            tuning=TuningConfig(mode="peqa"),
            quant=QuantConfig(bits=4, n_grid=2))
        api = registry.build(cfg)
        caps = api.caps
        assert caps is not None, arch
        if caps.slotted_reason is None:
            assert api.prefill_slotted is not None, arch
        if caps.prefix_required:
            assert caps.prefix_key is not None, arch


# ------------------------------------------------- structural cache oracles

def test_whisper_cross_kv_is_position_free():
    """The seq-dim oracle marks whisper's self-KV with its seq axis and the
    cross-KV (fixed encoder extent) with -1 — that split IS the protocol:
    paged growth for one, admit-once row writes for the other."""
    eng, cfg = _make_engine("whisper-medium")
    bdims, sdims = eng._cache_dims()
    for name in ("k", "v"):
        assert sdims[name] == 2, (name, sdims[name])
    for name in ("xk", "xv"):
        assert sdims[name] == -1, (name, sdims[name])
        assert bdims[name] == 1, (name, bdims[name])


def test_recurrent_state_is_all_position_free():
    """SSM/recurrent families have NO positional cache leaf: every slot
    admit is a pure batch-row write and capacity checks are meaningless."""
    for arch in ("xlstm-125m", "zamba2-7b"):
        eng, cfg = _make_engine(arch)
        _, sdims = eng._cache_dims()
        leaves = jax.tree.leaves(sdims)
        if cfg.family == "ssm":
            assert all(sd == -1 for sd in leaves), (arch, sdims)
            assert not eng._has_seq_leaf()
        else:  # hybrid: recurrent rows -1 AND attention KV paged
            assert any(sd == -1 for sd in leaves), (arch, sdims)
            assert any(sd >= 0 for sd in leaves), (arch, sdims)
            assert eng._has_seq_leaf()


def test_grow_cache_passes_position_free_leaves_through():
    """Growing a whisper cache stretches the self-KV seq dim and hands the
    cross-KV back UNTOUCHED (equal shapes short-circuit); tampering with a
    position-free leaf's extent must raise, not silently 'grow'."""
    eng, cfg = _make_engine("whisper-medium")
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 5)),
                              jnp.int32),
        "frames": jnp.asarray(rng.normal(size=(2, cfg.enc_frames,
                                               cfg.d_model)), jnp.float32),
    }
    _, cache = eng._prefill(eng.params, batch)
    grown = eng._grow_cache(cache, 2, 16, 5)
    assert grown["k"].shape[2] == 16
    np.testing.assert_array_equal(np.asarray(grown["xk"]),
                                  np.asarray(cache["xk"]))
    assert grown["xv"] is cache["xv"]
    bad = dict(cache)
    bad["xk"] = jnp.concatenate([cache["xk"], cache["xk"]], axis=2)
    with pytest.raises(ValueError, match="seq dim"):
        eng._grow_cache(bad, 2, 16, 5)


def test_cache_seq_dims_oracle_marks_position_free_minus_one():
    """The dist-layer oracle itself (what ``_cache_dims`` consumes):
    whisper cross-KV and every xlstm leaf probe as -1."""
    for arch, expect_any_seq in (("whisper-medium", True),
                                 ("xlstm-125m", False)):
        cfg = configs.make_tiny(configs.get_config(arch)).replace(
            tuning=TuningConfig(mode="peqa"),
            quant=QuantConfig(bits=4, n_grid=2))
        api = registry.build(cfg)
        sdims = shard_rules.cache_seq_dims(api.init_cache, 2, 8)
        leaves = jax.tree.leaves(sdims)
        assert any(sd >= 0 for sd in leaves) == expect_any_seq, (arch, sdims)
        assert any(sd == -1 for sd in leaves), (arch, sdims)


# ------------------------------------------------------- prefix validation

def test_prefix_rejected_for_prefixless_family():
    eng, cfg = _make_engine("xlstm-125m")
    pool = eng.open_pool(2, 32)
    with pytest.raises(ValueError, match="no per-request prefix"):
        eng.admit(pool, Request(
            tokens=np.arange(8, dtype=np.int32), n_new=2,
            prefix=np.zeros((4, cfg.d_model), np.float32)))


def test_missing_required_prefix_rejected():
    eng, cfg = _make_engine("whisper-medium")
    with pytest.raises(ValueError, match="requires prefix"):
        eng.generate(jnp.zeros((1, 4), jnp.int32), n_new=2)
    pool = eng.open_pool(2, 32)
    with pytest.raises(ValueError, match="requires prefix"):
        eng.admit(pool, Request(tokens=np.arange(4, dtype=np.int32),
                                n_new=2))


def test_vlm_prefix_occupies_decoder_positions():
    """Image-embedding rows consume slot cache capacity: a request whose
    prompt+prefix+budget overflows the pool must be refused at admit."""
    eng, cfg = _make_engine("llava-next-mistral-7b")
    pool = eng.open_pool(2, 16)
    prefix = np.zeros((cfg.n_img_tokens, cfg.d_model), np.float32)
    with pytest.raises(ValueError, match="cache slots"):
        eng.admit(pool, Request(tokens=np.arange(6, dtype=np.int32),
                                n_new=4, prefix=prefix))
