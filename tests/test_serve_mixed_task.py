"""Drain-free mixed-task serving: the resident scheduler vs drain.

The acceptance contract (ISSUE: fused GEMV + in-kernel task gather):

  * token-for-token equality — every request decodes the exact tokens the
    drain-then-swap scheduler produces (the slotted kernels compute each
    task's rows with the plain path's expression, tests/test_gemv.py);
  * ZERO task-drain idle slot-steps under ``resident`` (the drain tax the
    stacked scales delete), positive under ``drain`` on the same traffic;
  * fewer decode steps (the wall-clock win, counted deterministically);
  * honest degradation: a stack smaller than the task set LRU-evicts, a
    fully pinned stack stalls admission WITHOUT deadlock, and both are
    metered, never silent.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.core import scale_bank as sb
from repro.dist import sharding as shard_rules
from repro.models import registry
from repro.serve import ServeConfig
from repro.train.serve import Engine, Request

TASKS = ("t0", "t1", "t2")


def _setup(quant):
    cfg = configs.paper_lm(n_layers=2, d_model=64, n_heads=2, d_ff=96,
                           vocab=128).replace(
        tuning=TuningConfig(mode="peqa"), quant=quant)
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)          # host master (swaps may donate)
    bank = sb.ScaleBank()
    bank.add(TASKS[0], p)
    rngs = np.random.default_rng(7)
    for t in TASKS[1:]:
        bank.tasks[t] = {k: (v * rngs.uniform(0.8, 1.2, v.shape)
                             ).astype(v.dtype)
                         for k, v in bank.tasks[TASKS[0]].items()}
    return cfg, api, p, bank


@pytest.fixture(scope="module")
def setup():
    return _setup(QuantConfig(bits=4, n_grid=2))


@pytest.fixture(scope="module")
def plane_setup():
    """A 3-bit bit-plane backbone, the layout the tune cell runs."""
    return _setup(QuantConfig(bits=3, n_grid=2, layout="plane"))


def _engine(setup, with_bank=True):
    cfg, api, p, bank = setup
    return Engine(api, jax.tree.map(jnp.asarray, p),
                  bank=bank if with_bank else None)


def _requests(cfg, n=9):
    return [Request(
        tokens=(np.arange(4, dtype=np.int32) * (i + 1)) % cfg.vocab_size,
        n_new=(4, 6, 8)[i % 3], task=TASKS[i % 3]) for i in range(n)]


@pytest.fixture(scope="module")
def drain_report(setup):
    cfg = setup[0]
    return _engine(setup).serve(
        _requests(cfg), ServeConfig(n_slots=3, scheduler="drain"))


def test_resident_token_equal_and_drain_free(setup, drain_report):
    cfg = setup[0]
    rep = _engine(setup).serve(_requests(cfg),
                               ServeConfig(n_slots=3, scheduler="auto"))
    assert rep.scheduler == "resident"
    assert drain_report.scheduler == "drain"
    assert rep.tokens == drain_report.tokens          # token-for-token
    assert all(t is not None for t in rep.tokens)
    assert rep.task_drain_idle_slot_steps == 0
    assert drain_report.task_drain_idle_slot_steps > 0
    assert rep.steps < drain_report.steps
    assert rep.resident_installs == len(TASKS)        # one install per task
    assert rep.bubble_slot_steps == 0


def test_lru_small_stack_still_exact(setup, drain_report):
    """capacity 2 < 3 tasks: rows churn (installs > task count), admission
    stalls on pinned rows are metered, tokens stay EXACT — and more slots
    than resident rows (4 > 2) cannot deadlock the admission loop."""
    cfg = setup[0]
    rep = _engine(setup).serve(
        _requests(cfg),
        ServeConfig(n_slots=3, scheduler="resident", resident_tasks=2))
    assert rep.tokens == drain_report.tokens
    assert rep.resident_installs > len(TASKS)         # LRU churn
    rep4 = _engine(setup).serve(
        _requests(cfg),
        ServeConfig(n_slots=4, scheduler="resident", resident_tasks=2))
    assert rep4.tokens == drain_report.tokens
    assert all(t is not None for t in rep4.tokens)


def test_resident_prefill_reads_stack_zero_swaps(setup, drain_report):
    """Admission under resident is swap-free: prefill runs through the
    ResidentStack row (``prefill_slotted``), so the live params are never
    re-targeted at admit — ``switches == 0`` while drain pays one swap per
    task run on the same traffic.  The only scale traffic left is the row
    installs themselves, and tokens still match drain exactly."""
    cfg = setup[0]
    rep = _engine(setup).serve(
        _requests(cfg), ServeConfig(n_slots=3, scheduler="resident"))
    assert rep.switches == 0
    assert rep.resident_installs == len(TASKS)
    assert drain_report.switches > 0
    assert rep.tokens == drain_report.tokens


def test_auto_falls_back_to_drain_when_untasked(setup, drain_report):
    cfg = setup[0]
    reqs = _requests(cfg, n=3)
    reqs[1] = Request(tokens=reqs[1].tokens, n_new=reqs[1].n_new)  # no task
    rep = _engine(setup).serve(reqs, ServeConfig(n_slots=3, scheduler="auto"))
    assert rep.scheduler == "drain"


def test_explicit_resident_raises_when_unsupported(setup):
    cfg, api, p, bank = setup
    reqs = [Request(tokens=np.arange(4, dtype=np.int32), n_new=4)]
    with pytest.raises(ValueError, match="names a task"):
        _engine(setup).serve(reqs,
                             ServeConfig(n_slots=2, scheduler="resident"))
    nobank = Engine(api, jax.tree.map(jnp.asarray, p))
    with pytest.raises(ValueError, match="ScaleBank"):
        nobank.serve(_requests(cfg, n=3),
                     ServeConfig(n_slots=2, scheduler="resident"))
    with pytest.raises(ValueError, match="unknown scheduler"):
        ServeConfig(n_slots=2, scheduler="residnet")


@pytest.mark.parametrize("case", ["untasked", "resident"])
def test_plane_backbone_serves_exactly(plane_setup, case):
    """A bit-plane backbone through ``Engine.serve``: untasked continuous
    serving (``auto``, no bank) emits each request's lockstep ``generate``
    tokens; tasked, the resident scheduler emits the drain scheduler's
    tokens and never idles a slot on a task switch."""
    cfg = plane_setup[0]
    reqs = _requests(cfg)
    if case == "untasked":
        reqs = [Request(tokens=r.tokens, n_new=r.n_new) for r in reqs]
        eng = _engine(plane_setup, with_bank=False)
        rep = eng.serve(reqs, ServeConfig(n_slots=3, scheduler="auto"))
        want = [list(np.asarray(eng.generate(
            jnp.asarray(r.tokens)[None], n_new=r.n_new))[0, len(r.tokens):])
            for r in reqs]
    else:
        rep = _engine(plane_setup).serve(
            reqs, ServeConfig(n_slots=3, scheduler="resident"))
        drain = _engine(plane_setup).serve(
            reqs, ServeConfig(n_slots=3, scheduler="drain"))
        assert rep.scheduler == "resident" and drain.scheduler == "drain"
        assert rep.task_drain_idle_slot_steps == 0
        want = drain.tokens
    assert all(t is not None for t in rep.tokens)
    assert rep.tokens == want


def test_resident_stack_row_content(setup):
    """ensure() installs exactly the bank's scale rows (base zeros ride
    along frozen for paths the task set lacks)."""
    cfg, api, p, bank = setup
    base = sb.extract_scales(jax.tree.map(jnp.asarray, p), include_zero=True)
    rs = sb.ResidentStack(bank, jax.tree.map(jnp.asarray, p), capacity=2)
    row = rs.ensure("t1")
    assert rs.names[row] == "t1"
    for kp, leaf in jax.tree_util.tree_leaves_with_path(rs.stack):
        path = "/".join(str(k.key) for k in kp)
        want = np.asarray(bank.tasks["t1"].get(path, base[path]))
        got = np.asarray(jnp.take(leaf, row, axis=leaf.ndim - 3))
        np.testing.assert_array_equal(got, want.astype(got.dtype))


def test_resident_stack_lru_pinning(setup):
    cfg, api, p, bank = setup
    rs = sb.ResidentStack(bank, jax.tree.map(jnp.asarray, p), capacity=2,
                          warm=("t0",))
    # empty rows are preferred over evicting a resident task
    r1 = rs.ensure("t1")
    assert rs.names.count(None) == 0 and "t0" in rs.names
    # full + everything pinned -> None (caller decodes a step and retries)
    assert rs.ensure("t2", pinned={"t0", "t1"}) is None
    # pinned rows are never the victim
    r2 = rs.ensure("t2", pinned={"t1"})
    assert r2 != r1 and rs.names[r1] == "t1" and rs.names[r2] == "t2"
    # LRU order: touching t1 makes t2 the next victim
    rs.ensure("t1")
    r0 = rs.ensure("t0", pinned=())
    assert r0 == r2
    with pytest.raises(KeyError):
        rs.ensure("nope")


def test_stacked_scale_specs(setup):
    """Trailing-relative stacked specs: the task dim is replicated, column
    scales keep the model axis on the out dim, row-parallel scales stay
    replicated — so a row install moves the same per-shard bytes as a swap
    and the in-kernel gather needs no collective."""
    z = lambda: np.zeros((2, 3, 64, 4), np.float32)
    tree = {"layers": {"attn": {"wq": {"scale": z(), "zero": z()},
                                "wo": {"scale": z()}},
                       "mlp": {"down": {"scale": z()}}}}
    specs = shard_rules.stacked_scale_specs(tree)
    P = jax.sharding.PartitionSpec
    assert specs["layers"]["attn"]["wq"]["scale"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wq"]["zero"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wo"]["scale"] == P()
    assert specs["layers"]["mlp"]["down"]["scale"] == P()
    with pytest.raises(ValueError, match="non-scale leaf"):
        shard_rules.stacked_scale_specs(
            {"layers": {"attn": {"wq": {"w": z()}}}})
