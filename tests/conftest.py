"""Shared test helpers."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.serve import Request

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def subproc_env() -> dict:
    """Env for subprocess tests: repo src on the path, CPU pinned (a libtpu
    is present in some images and every fresh process would otherwise burn
    ~2 min failing TPU init before falling back)."""
    return {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
            "HOME": os.environ.get("HOME", "/root"), "JAX_PLATFORMS": "cpu"}


def assert_order_close(got, want, x, qt, spec, scale=None, zero=None,
                       task_ids=None):
    """|got − want| within the f32 summation-order bound (ref.ORDER_ULPS)."""
    tol = ref.f32_order_tolerance(
        x, qt.qw, qt.scale if scale is None else scale,
        qt.zero if zero is None else zero, qt.shape, spec, task_ids)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert np.all(err <= np.asarray(tol)), float(np.max(err - tol))


def _admit(eng, pool, tokens, n_new, task):
    """Admit one request as the resident serving loop does: a tasked
    request prefills and decodes from its resident stack row."""
    req = Request(tokens=np.asarray(tokens, np.int32), n_new=n_new,
                  task=task)
    if task is None:
        return eng.admit(pool, req)
    row = eng.resident.ensure(task)
    slot = eng.admit(pool, req, task_row=row)
    pool.tid[slot] = row
    pool._dev = None
    return slot


def stale_rows_tokens(eng, pool, tasks=(None, None), n_steps=4):
    """Leave stale cache rows behind through the kept serving path, then
    decode ``pool`` and a copy whose float rows at or past each slot's
    ``pos`` hold 1e4; returns both token streams.

    A long and a short request are admitted and stepped, the long one is
    evicted and a shorter request takes its slot, so its rows from the new
    ``pos`` up to the old one still hold the long request's K/V.  Equal
    streams mean no decode read a row past its slot's ``pos``: a leaked
    1e4 row would dominate the softmax and flip the argmax.  The sentinel
    is finite because masked attention weighs dead rows by an exact 0,
    which cancels a finite value but would carry a NaN through.
    """
    vocab = eng.api.cfg.vocab_size
    rng = np.random.default_rng(5)
    long_slot = _admit(eng, pool, rng.integers(0, vocab, 12), 10, tasks[0])
    _admit(eng, pool, rng.integers(0, vocab, 4), 10, tasks[1])
    eng.step(pool)
    written = int(pool.pos[long_slot])
    eng.evict(pool, long_slot)
    assert _admit(eng, pool, rng.integers(0, vocab, 2), 10,
                  tasks[1]) == long_slot
    assert pool.pos[long_slot] < written     # stale rows past pos exist

    bdims, sdims = eng._cache_dims()

    def poison(leaf, bd, sd):
        a = np.array(leaf)                   # a copy: steps donate the pool
        if bd >= 0 and sd >= 0 and jnp.issubdtype(a.dtype, jnp.floating):
            for slot in range(pool.n_slots):
                idx = [slice(None)] * a.ndim
                idx[bd], idx[sd] = slot, slice(int(pool.pos[slot]), None)
                a[tuple(idx)] = 1e4
        return jnp.asarray(a)

    twin = copy.copy(pool)
    for name in ("pos", "active", "tok", "tid"):
        setattr(twin, name, getattr(pool, name).copy())
    twin.meta, twin.task = copy.deepcopy(pool.meta), list(pool.task)
    twin._dev = None
    twin.cache = jax.tree.map(poison, pool.cache, bdims, sdims)
    clean = [eng.step(pool).tolist() for _ in range(n_steps)]
    poisoned = [eng.step(twin).tolist() for _ in range(n_steps)]
    return clean, poisoned
