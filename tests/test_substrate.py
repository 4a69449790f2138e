"""Substrate tests: optimizer, schedules, data pipeline, checkpointing,
gradient compression, watchdog — the fault-tolerance contract."""
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # image has no hypothesis; see fallback
    from _hypothesis_fallback import given, settings, st

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import OptimConfig
from repro.data import pipeline, synthetic
from repro.optim import compression
from repro.optim.adamw import make_optimizer
from repro.optim.schedules import make_schedule
from repro.train.loop import Watchdog


# --------------------------------------------------------------------- optim

def test_masked_optimizer_state_only_for_trainable():
    params = {"a": {"w": jnp.ones((8, 8))}, "b": {"scale": jnp.ones((8, 1))}}
    mask = {"a": {"w": False}, "b": {"scale": True}}
    opt = make_optimizer(OptimConfig(), 10)
    st_ = opt.init(params, mask)
    assert opt.state_bytes(st_) == 2 * 8 * 1 * 4  # two f32 moments for scale
    grads = {"a": {"w": jnp.ones((8, 8))}, "b": {"scale": jnp.ones((8, 1))}}
    newp, st2, gnorm = opt.update(grads, st_, params, mask)
    np.testing.assert_array_equal(np.asarray(newp["a"]["w"]),
                                  np.asarray(params["a"]["w"]))  # frozen
    assert not np.array_equal(np.asarray(newp["b"]["scale"]),
                              np.asarray(params["b"]["scale"]))  # trained
    assert float(gnorm) == pytest.approx(np.sqrt(8.0), rel=1e-5)


def test_adamw_converges_quadratic():
    opt = make_optimizer(OptimConfig(lr=0.1, warmup_steps=1,
                                     schedule="constant"), 200)
    params = {"x": jnp.asarray(5.0)}
    mask = {"x": True}
    st_ = opt.init(params, mask)
    for _ in range(200):
        g = {"x": 2 * params["x"]}
        params, st_, _ = opt.update(g, st_, params, mask)
    assert abs(float(params["x"])) < 0.05


def test_schedule_shapes():
    ocfg = OptimConfig(lr=1e-3, warmup_steps=10, schedule="linear")
    sched = make_schedule(ocfg, 100)
    assert float(sched(0)) == 0.0
    assert float(sched(10)) == pytest.approx(1e-3, rel=1e-3)
    assert float(sched(100)) == pytest.approx(0.0, abs=1e-9)
    assert float(sched(55)) == pytest.approx(0.5e-3, rel=0.02)


# ---------------------------------------------------------------------- data

def test_pipeline_deterministic_and_resumable():
    toks = synthetic.corpus(128, 20000, seed=0)
    d = pipeline.PackedLM(toks, batch_size=4, seq_len=32)
    b5a = d.batch_at(5)
    b5b = d.batch_at(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    # labels are tokens shifted by one
    np.testing.assert_array_equal(b5a["labels"][:, :-1], b5a["tokens"][:, 1:])


def test_pipeline_host_sharding_partitions_batch():
    toks = synthetic.corpus(64, 10000, seed=0)
    full = pipeline.PackedLM(toks, batch_size=4, seq_len=16)
    h0 = pipeline.PackedLM(toks, batch_size=4, seq_len=16, host_id=0,
                           host_count=2)
    h1 = pipeline.PackedLM(toks, batch_size=4, seq_len=16, host_id=1,
                           host_count=2)
    got = np.concatenate([h0.batch_at(3)["tokens"], h1.batch_at(3)["tokens"]])
    np.testing.assert_array_equal(got, full.batch_at(3)["tokens"])


def test_synthetic_corpus_has_structure():
    toks = synthetic.corpus(256, 50000, seed=0)
    h1 = synthetic.unigram_entropy(toks, 256)
    # bigram entropy must be substantially below unigram (learnable signal)
    pairs = toks[:-1].astype(np.int64) * 256 + toks[1:]
    h2 = synthetic.unigram_entropy(pairs, 256 * 256) - h1
    assert h2 < h1 - 0.5


# ---------------------------------------------------------------------- ckpt

def _tree():
    return {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.asarray(3), "d": (np.ones(2), np.zeros(1))}}


def test_ckpt_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    mgr.save(10, t, extra={"note": "x"})
    out, extra = mgr.restore(t)
    assert extra["step"] == 10 and extra["note"] == "x"
    np.testing.assert_array_equal(out["a"], t["a"])
    np.testing.assert_array_equal(out["b"]["d"][0], t["b"]["d"][0])


def test_ckpt_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [3, 4]


def test_ckpt_torn_write_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, _tree())
    mgr.save(2, _tree())
    # corrupt the newest payload
    with open(os.path.join(str(tmp_path), "step_00000002", "arrays.npz"),
              "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad\xbe\xef")
    assert mgr.latest_valid_step() == 1
    out, extra = mgr.restore(_tree())
    assert extra["step"] == 1


@pytest.mark.parametrize("has_qw", [True, False])
def test_ckpt_refuses_other_qw_packing(tmp_path, has_qw):
    """A checkpoint whose manifest predates the packing version (layout 1)
    restores only when it holds no packed codes."""
    tree = {"lin": {("qw" if has_qw else "w"): np.arange(4, dtype=np.uint32),
                    "scale": np.ones(2, np.float32)}}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, tree)
    out, _ = mgr.restore(tree)                 # same version: restores
    np.testing.assert_array_equal(out["lin"]["scale"], tree["lin"]["scale"])
    path = os.path.join(str(tmp_path), "step_00000003", "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    del manifest["qw_packing"]
    with open(path, "w") as f:
        json.dump(manifest, f)
    if has_qw:
        with pytest.raises(ValueError, match="layout 1"):
            mgr.restore(tree)
    else:
        out, extra = mgr.restore(tree)
        assert extra["step"] == 3


def test_ckpt_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    mgr.save(5, _tree())
    mgr.wait()
    assert mgr.latest_valid_step() == 5


# --------------------------------------------------------------- compression

@given(st.integers(0, 10_000), st.floats(0.01, 100.0))
@settings(max_examples=25, deadline=None)
def test_int8_compression_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * scale)
    q, s = compression.compress(g)
    back = compression.decompress(q, s)
    # error ≤ scale/2 per element = max|g|/254
    assert float(jnp.max(jnp.abs(back - g))) <= float(jnp.max(jnp.abs(g))) / 254 + 1e-6


def test_compress_tree_respects_mask():
    vals = jnp.asarray([0.1, 0.033, -0.07, 1.0])  # not exactly representable
    g = {"a": vals, "b": vals}
    out = compression.compress_tree(g, {"a": True, "b": False})
    assert not np.array_equal(np.asarray(out["a"]), np.asarray(g["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(g["b"]))


# ------------------------------------------------------------------ watchdog

def test_watchdog_flags_hang():
    events = []
    wd = Watchdog(0.15, on_hang=lambda dt: events.append(dt))
    wd.step_begin()
    time.sleep(0.4)
    wd.step_end()
    wd.close()
    assert events, "watchdog did not fire on a hung step"
    assert wd.slowest >= 0.35


def test_watchdog_quiet_on_fast_steps():
    events = []
    wd = Watchdog(0.5, on_hang=lambda dt: events.append(dt))
    for _ in range(3):
        wd.step_begin()
        time.sleep(0.01)
        wd.step_end()
    wd.close()
    assert not events


# ------------------------------------------------------------- compile cache

@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, stands as JAX read it; otherwise
    the cache goes to the fixed <checkout>/.jax_cache."""
    from repro.dist import backend

    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = backend.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert got == str(backend.CHECKOUT / ".jax_cache")
            assert (backend.CHECKOUT / "pyproject.toml").exists()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
