"""A run with the timed path broken underneath comes out not correct, once
for each fault the cell can have.  The harness's look for a chip is
skipped (the rehearsal entry); everything else is a benchmark run at a
tiny size, judged by the cell's own limits.  One chip has no exchange
between chips to leave out."""
import jax
import jax.numpy as jnp

import run
import train_loop
from repro.core.scale_bank import ResidentStack
from repro.train.serve import Engine

import test_control
from test_rehearsal import SEED, SIZES, small_tune  # noqa: F401


def test_token_altered_where_produced(monkeypatch):
    real = Engine.step

    def step(self, pool):
        nxt = real(self, pool)
        for slot in range(pool.n_slots):
            if pool.active[slot]:
                out = pool.meta[slot]["out"]
                out[-1] = (out[-1] + 1) % self.api.cfg.vocab_size
                pool.tok[slot] = out[-1]
        pool._dev = None
        return nxt
    monkeypatch.setattr(Engine, "step", step)
    r = run.run_cell(run.cell("starcoder2-7b-w4.chat"), SEED, 3.0, False,
                     sizes=SIZES)
    assert not r["correct"], r["check"]


def test_wrong_task_row(monkeypatch):
    """Every request is served from the resident stack row after its own:
    another task's scales, in prefill (``task_row``) and decode
    (``pool.tid``) alike."""
    real = ResidentStack.ensure

    def ensure(self, name, *a, **kw):
        row = real(self, name, *a, **kw)
        return None if row is None else (row + 1) % self.capacity
    monkeypatch.setattr(ResidentStack, "ensure", ensure)
    # tasks differ by a few percent a scale: at the smallest size a
    # handful of served tokens rarely flips, so this runs at the control
    # test's size and served tokens
    w = run.cell("starcoder2-7b-w4.chat")
    r = run.run_cell(w, 11, 6.0, False, sizes=test_control.SIZES,
                     tr=test_control.serve_mix(w))
    assert not r["correct"], r["check"]


def _tune(monkeypatch, breaks=None):
    """A tune rehearsal's compared numbers, with the step broken by
    ``breaks`` (or sound)."""
    if breaks is not None:
        _broken_step(monkeypatch, breaks)
    r = run.run_cell(run.cell("mistral-7b-w3.tune"), SEED, 1.0, False,
                     sizes=SIZES)
    return r["correct"], {k: v["value"] for k, v in r["check"].items()}


def _caught(monkeypatch, breaks):
    """The broken step comes out not correct, and on a number that it
    moves well past a sound run's reading (at this size bfloat16 rounding
    alone weighs more than at the cell's, so the sound run is the
    yardstick, beside the cell's limits)."""
    _, sound = _tune(monkeypatch)
    correct, broken = _tune(monkeypatch, breaks)
    assert not correct, broken
    assert any(broken[k] > 3 * sound[k] for k in sound), (sound, broken)


def _broken_step(monkeypatch, breaks):
    real = train_loop.train_step.build_train_step

    def build(*a, **kw):
        return breaks(real(*a, **kw))
    monkeypatch.setattr(train_loop.train_step, "build_train_step", build)


def test_state_returned_unchanged(monkeypatch, small_tune):  # noqa: F811
    def breaks(fn):
        def step(state, batch):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = fn(state, batch)
            return keep, metrics
        return step
    _caught(monkeypatch, breaks)


def test_half_batch_left_out(monkeypatch, small_tune):  # noqa: F811
    def breaks(fn):
        def step(state, batch):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return fn(state, half)
        return step
    _caught(monkeypatch, breaks)
