"""Program spans (``repro.serve.telemetry.span``): the recorder's nesting,
ids and bound, and the spans ``Engine.admit``, ``Engine.step`` and
``ResidentStack.ensure`` record, at a CPU size."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig, TuningConfig
from repro.core import policies
from repro.core import scale_bank as sb
from repro.models import registry
from repro.serve import telemetry
from repro.train.serve import Engine, Request

TASKS = ("t0", "t1", "t2")
PROGRAM_SPANS = {"engine.step", "engine.step.wait", "engine.admit",
                 "engine.admit.wait", "stack.install"}


def test_span_parent_ids_and_nesting():
    rec = telemetry.SpanRecorder()
    with rec.span("outer", rid=7) as outer:
        with rec.span("inner.a"):
            pass
        with rec.span("inner.b") as b:
            b.set(bytes=12)
            with rec.span("leaf"):
                pass
        outer.set(late=True)
    got = {s.name: s for s in rec.spans()}
    assert [s.name for s in rec.spans()] == ["inner.a", "leaf", "inner.b",
                                            "outer"]    # in finishing order
    o = got["outer"]
    assert o.parent is None and o.attrs == {"rid": 7, "late": True}
    assert got["inner.a"].parent == o.id and got["inner.b"].parent == o.id
    assert got["leaf"].parent == got["inner.b"].id
    assert got["inner.b"].attrs == {"bytes": 12}
    assert len({s.id for s in rec.spans()}) == 4
    for child, parent in (("inner.a", "outer"), ("inner.b", "outer"),
                          ("leaf", "inner.b")):
        c, p = got[child], got[parent]
        assert p.start_ns <= c.start_ns <= c.end_ns <= p.end_ns
        assert c.duration_ns == c.end_ns - c.start_ns >= 0
    assert rec.spans("leaf") == [got["leaf"]]


def test_span_parent_is_per_thread_and_kept_on_error():
    rec = telemetry.SpanRecorder()
    with rec.span("main"):
        t = threading.Thread(target=lambda: rec.span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with pytest.raises(RuntimeError):
            with rec.span("fails"):
                raise RuntimeError("boom")
        with rec.span("after"):
            pass
    got = {s.name: s for s in rec.spans()}
    assert got["other"].parent is None           # another thread's stack
    assert got["fails"].parent == got["main"].id
    assert got["after"].parent == got["main"].id  # the stack was popped


def test_ring_is_bounded():
    rec = telemetry.SpanRecorder(maxlen=4)
    for i in range(10):
        with rec.span("even" if i % 2 == 0 else "odd", i=i):
            pass
    assert [s.attrs["i"] for s in rec.spans()] == [6, 7, 8, 9]
    assert [s.attrs["i"] for s in rec.spans("odd")] == [7, 9]
    assert telemetry.SpanRecorder()._ring.maxlen == 65536


@pytest.fixture(scope="module")
def setup():
    cfg = configs.paper_lm(n_layers=2, d_model=64, n_heads=2, d_ff=96,
                           vocab=128).replace(
        tuning=TuningConfig(mode="peqa"),
        quant=QuantConfig(bits=4, n_grid=2))
    api = registry.build(cfg)
    rng = jax.random.PRNGKey(0)
    p, _ = policies.prepare(api.init(rng), cfg, rng)
    p = jax.tree.map(np.asarray, p)
    bank = sb.ScaleBank()
    bank.add(TASKS[0], p)
    rngs = np.random.default_rng(7)
    for t in TASKS[1:]:
        bank.tasks[t] = {k: (v * rngs.uniform(0.8, 1.2, v.shape)
                             ).astype(v.dtype)
                         for k, v in bank.tasks[TASKS[0]].items()}
    return api, p, bank


def _new_spans(before):
    """Spans recorded by the default recorder since ``before`` (an id)."""
    return [s for s in telemetry.spans() if s.id > before]


def _last_id():
    s = telemetry.spans()
    return max((x.id for x in s), default=0)


@pytest.fixture
def served(setup):
    """An engine with a two-row resident stack and an open slotted pool."""
    api, p, bank = setup
    eng = Engine(api, jax.tree.map(jnp.asarray, p), bank=bank)
    eng.resident = sb.ResidentStack(bank, eng.params, 2, warm=TASKS[:2])
    pool = eng.open_pool(3, 16)
    pool.slotted = True
    return eng, pool


def _admit(eng, pool, rid, n_prompt, task="t0"):
    row = eng.resident.ensure(task)
    slot = eng.admit(pool, Request(tokens=np.arange(n_prompt, dtype=np.int32),
                                   n_new=4, task=task),
                     rid=rid, task_row=row)
    pool.tid[slot] = row
    pool._dev = None
    return slot, row


def test_admit_records_span_with_wait_child_and_rid(served):
    eng, pool = served
    before = _last_id()
    _, row = _admit(eng, pool, rid=41, n_prompt=5, task="t1")
    new = _new_spans(before)
    assert [s.name for s in new] == ["engine.admit.wait", "engine.admit"]
    wait, adm = new
    assert adm.attrs == {"rid": 41, "task_row": row, "prompt": 5,
                         "bucket": Engine._bucket_len(5, pool.cache_len)}
    assert wait.parent == adm.id and wait.attrs == {"rid": 41}
    assert adm.start_ns <= wait.start_ns <= wait.end_ns <= adm.end_ns


def test_step_records_one_span_with_one_wait_child(served):
    eng, pool = served
    _admit(eng, pool, rid=0, n_prompt=3)
    _admit(eng, pool, rid=1, n_prompt=6, task="t1")
    for _ in range(3):
        live = int(pool.pos[pool.active].sum()) + pool.n_active()
        before = _last_id()
        eng.step(pool)
        new = _new_spans(before)
        assert [s.name for s in new] == ["engine.step.wait", "engine.step"]
        wait, step = new
        assert wait.parent == step.id
        assert step.attrs == {"active": 2, "kv_rows_live": live,
                              "kv_rows_read": pool.n_slots * pool.cache_len}
        assert step.start_ns <= wait.start_ns <= wait.end_ns <= step.end_ns


def test_ensure_records_install_on_miss_only(setup, served):
    eng, _ = served
    res = eng.resident
    before = _last_id()
    assert res.ensure("t0") == 0                  # hit: no span
    assert _new_spans(before) == []
    row = res.ensure("t2")                        # miss: installs a row
    (inst,) = _new_spans(before)
    assert inst.name == "stack.install" and inst.parent is None
    host = res._rows_for("t2")
    assert inst.attrs == {"task": "t2", "row": row,
                          "bytes": sum(a.nbytes
                                       for a in jax.tree.leaves(host))}
    assert inst.attrs["bytes"] > 0
    before = _last_id()
    assert res.ensure("t2") == row and _new_spans(before) == []


def test_program_records_only_its_spans(served):
    eng, pool = served
    before = _last_id()
    slot, _ = _admit(eng, pool, rid=3, n_prompt=4, task="t2")
    eng.step(pool)
    eng.evict(pool, slot)
    assert {s.name for s in _new_spans(before)} <= PROGRAM_SPANS
