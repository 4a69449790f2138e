"""The serve loop layer: a wall-clock loop over the engine's per-step entry
points (``Engine.open_pool``, ``Engine.admit(task_row=…)``, ``Engine.step``,
``Engine.evict``), with FIFO admission and immediate eviction as
``Engine.serve`` has them, and the resident task stack
(``core.scale_bank.ResidentStack``) holding the hottest tasks' scales.

``Engine.serve`` runs on a virtual clock whose step and prefill times are
inputs, so it cannot be driven as an open loop; this loop keeps its own
clock.  It reaches into the engine where ``Engine.serve`` does for itself
and the engine has no public entry: it sets ``engine.resident``,
``pool.slotted`` and ``pool.tid[slot]``, drops the pool's device copy of
its per-slot inputs after a ``tid`` write (``pool._dev = None``), asks
``Engine._slot_done`` whether a slot has finished, and buckets prompts by
``Engine._bucket_len``.

``Job`` is the serving mode of a benchmark run (``run.py`` finds it as
``<mode>_loop.Job`` from the mix's ``mode``): set-up, window, and the
comparison with the configuration's reference.

Every call into a layer is wrapped in a ``jax.profiler.TraceAnnotation``
(``serve.admit``, ``serve.install``, ``serve.decode``, ``serve.evict``,
``serve.wait``) so that a traced run can say what the host was doing in
each idle gap of the device.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import numpy as np

import check
import model
import traffic
from repro.core.scale_bank import ResidentStack, ScaleBank, extract_scales
from repro.train.serve import Engine, Request as EngineRequest

Span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Record:
    """What the window saw of one request (host seconds from window start)."""
    rid: int
    task: int
    n_prompt: int
    n_new: int
    due: float
    admit: float | None = None
    first: float | None = None
    last: float | None = None
    n_out: int = 0
    done: bool = False
    tokens: list | None = None


@dataclasses.dataclass
class Step:
    """One decode step: start and end (window seconds), active slots, the
    KV positions they attend over, and the distinct task rows they read."""
    start: float
    end: float
    active: int
    live_kv: int
    tasks: int


@dataclasses.dataclass
class Admit:
    start: float
    end: float
    n_prompt: int
    bucket: int
    installed: bool


class ServeLoop:
    def __init__(self, engine: Engine, tr: dict, reqs: list, ranked):
        """``ranked``: task ids by popularity, hottest first.  The stack
        starts with the hottest ``resident_rows`` tasks, as a server that
        has run for a while holds them; requests for the others install a
        row in the window."""
        pool_cfg = tr["pool"]
        self.engine = engine
        self.tr = tr
        self.reqs = reqs
        self.n_slots = pool_cfg["slots"]
        self.cache_len = pool_cfg["cache_len"]
        self.closed = any(r.session is not None for r in reqs)
        # the coldest of the resident tasks sits least recently used, so the
        # first miss evicts it
        hot = [model.task_name(int(t))
               for t in ranked[:tr["tasks"]["resident_rows"]]]
        engine.resident = ResidentStack(engine.bank, engine.params,
                                        tr["tasks"]["resident_rows"],
                                        warm=hot[::-1])
        self.pool = engine.open_pool(self.n_slots, self.cache_len)
        self.pool.slotted = True

    # ------------------------------------------------------------- set-up
    def buckets(self) -> list:
        """(bucketed prompt length, padded?) of every request of the run:
        the prefill programs the window will call."""
        out = set()
        for r in self.reqs:
            s = len(r.tokens)
            b = Engine._bucket_len(s, self.cache_len)
            out.add((b, b != s))
        return sorted(out)

    def warm_up(self, vocab: int) -> None:
        """Compile (or load from the cache) every program the window calls:
        each prefill shape the run's prompts take, its slot write, the
        decode step, and a row install; then empty the pool again."""
        eng, pool = self.engine, self.pool
        rng = np.random.default_rng(0)
        res = eng.resident
        names = [model.task_name(t) for t in range(self.tr["tasks"]["count"])]
        cold = [n for n in names if n not in res.names]
        # a mix with more tasks than rows installs rows in the window: warm
        # the install program on one of the cold tasks
        task = cold[0] if cold else res.names[0]
        row = res.ensure(task)
        for bucket, padded in self.buckets():
            if pool.free_slot() is None:
                self._drain()
            s = bucket - 1 if padded else bucket
            slot = eng.admit(pool, EngineRequest(
                tokens=rng.integers(0, vocab, s).astype(np.int32), n_new=2,
                task=task), task_row=row)
            pool.tid[slot] = row
            pool._dev = None
            eng.step(pool)
        self._drain()
        jax.block_until_ready(pool.cache)

    def _drain(self) -> None:
        for slot in np.flatnonzero(self.pool.active):
            self.engine.evict(self.pool, int(slot))

    # ------------------------------------------------------------- window
    def fill(self) -> None:
        """Admit the requests in flight when the window opens (set-up).
        Their tokens count from the window's start."""
        self.recs = [Record(r.rid, r.task, len(r.tokens), r.n_new,
                            due=-1.0 if r.at_start else (
                                r.due_s if r.due_s is not None
                                else float("inf")))
                     for r in self.reqs]
        self.slot_rid = {}
        self.steps, self.admits = [], []
        if sum(r.at_start for r in self.reqs) > self.n_slots:
            raise ValueError("more requests in flight at the start than "
                             "the pool has slots")
        for r in self.reqs:
            if r.at_start:
                self._admit(r.rid, lambda: 0.0)
                self.recs[r.rid].n_out = 0
        self.admits.clear()

    def _admit(self, rid: int, clock):
        """Admit request ``rid`` if a stack row is free for its task;
        returns its slot, or None when every row is pinned."""
        eng, pool, res = self.engine, self.pool, self.engine.resident
        r = self.reqs[rid]
        name = model.task_name(r.task)
        pinned = {pool.task[s] for s in np.flatnonzero(pool.active)}
        hit = name in res.names
        with Span("serve.install"):
            row = res.ensure(name, pinned=pinned)
        if row is None:
            return None
        rec = self.recs[rid]
        rec.admit = clock()
        with Span("serve.admit"):
            slot = eng.admit(pool, EngineRequest(
                tokens=r.tokens, n_new=r.n_new, task=name),
                rid=rid, task_row=row)
        pool.tid[slot] = row
        pool._dev = None
        rec.first = clock()
        rec.n_out = 1
        self.slot_rid[slot] = rid
        self.admits.append(Admit(rec.admit, rec.first, len(r.tokens),
                                 Engine._bucket_len(len(r.tokens),
                                                    self.cache_len),
                                 not hit))
        return slot

    def run(self, seconds: float, on_time=None) -> dict:
        """Serve for ``seconds`` of wall time after ``fill``.  ``on_time(t)``
        is called between steps with the window time (the traced run starts
        its profiler from it).  Returns the window's records."""
        eng, pool, res = self.engine, self.pool, self.engine.resident
        reqs, recs, slot_rid = self.reqs, self.recs, self.slot_rid
        steps = self.steps
        installs0 = res.installs
        queue = collections.deque()
        if self.closed:
            sessions = collections.defaultdict(collections.deque)
            for r in reqs:
                sessions[r.session].append(r.rid)
            for q in sessions.values():
                rid = q.popleft()
                recs[rid].due = 0.0
                queue.append(rid)
            pending = collections.deque()
        else:
            pending = collections.deque(r.rid for r in reqs
                                        if not r.at_start)
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0

        def finish(slot: int, now: float) -> None:
            rid = slot_rid.pop(slot)
            with Span("serve.evict"):
                out = eng.evict(pool, slot)
            rec = recs[rid]
            rec.done, rec.last, rec.tokens = True, now, out
            if self.closed:
                nxt = sessions[reqs[rid].session]
                if nxt:
                    r2 = nxt.popleft()
                    recs[r2].due = now
                    queue.append(r2)

        while True:
            now = clock()
            if now >= seconds:
                break
            if on_time is not None:
                on_time(now)
            while pending and recs[pending[0]].due <= now:
                queue.append(pending.popleft())
            while queue and pool.free_slot() is not None:
                slot = self._admit(queue[0], clock)
                if slot is None:                  # every row pinned
                    break
                queue.popleft()
                if eng._slot_done(pool, slot):
                    finish(slot, recs[slot_rid[slot]].first)
            if pool.n_active():
                act = np.flatnonzero(pool.active)
                live = int(pool.pos[act].sum()) + len(act)
                ntask = len(set(pool.tid[act].tolist()))
                start = clock()
                with Span("serve.decode"):
                    eng.step(pool)
                end = clock()
                steps.append(Step(start, end, len(act), live, ntask))
                for slot in act:
                    rec = recs[slot_rid[int(slot)]]
                    rec.n_out += 1
                    rec.last = end
                    if eng._slot_done(pool, int(slot)):
                        finish(int(slot), end)
            else:
                nxt = recs[pending[0]].due if pending else seconds
                with Span("serve.wait"):
                    time.sleep(max(0.0, min(nxt, seconds) - clock()))
        window = clock()
        for slot, rid in slot_rid.items():   # in flight at the close
            recs[rid].tokens = list(pool.meta[slot]["out"])
        return {"records": recs, "steps": steps, "admits": self.admits,
                "window_s": window, "installs": res.installs - installs0}

    def free(self) -> None:
        """Drop the engine, its pool and its stack so the reference has the
        chip."""
        self.engine.resident = None
        self.engine = self.pool = None


class Job:
    """One run of a serving cell: the weights and task scale sets from the
    seed, the engine and the warmed loop (set-up), the window, and the
    comparison of a sample of what it served with the reference."""

    def __init__(self, cell, seed: int, seconds: float, mark):
        cfg, tr = cell.cfg, cell.tr
        self.cell, self.seed = cell, seed
        params = cell.builder.make_params(cfg, seed)
        jax.block_until_ready(params)
        mark("weights")
        self.reqs = traffic.serve_requests(tr, seed, seconds, cfg.vocab_size)
        bank = ScaleBank()
        bank.tasks.update(model.task_sets(
            extract_scales(params, include_zero=True), seed,
            tr["tasks"]["count"]))
        mark("traffic and task scale sets")
        self.loop = ServeLoop(Engine(cell.api, params, bank=bank), tr,
                              self.reqs, traffic.ranked_tasks(tr, seed))
        del params, bank
        mark("resident stack and pool")
        self.loop.warm_up(cfg.vocab_size)
        mark("warm-up")
        self.loop.fill()
        mark("in-flight requests admitted")

    def run(self, seconds: float, on_time=None) -> dict:
        out = self.loop.run(seconds, on_time=on_time)
        out["attempted"] = sum(1 for r in out["records"]
                               if r.due < out["window_s"])
        self.records = out["records"]
        return out

    def compare(self, ref, arch) -> dict:
        """Frees the program's state, then compares a sample of the finished
        requests drawn from the seed, the longest always in."""
        self.loop.free()
        c = self.cell.tr["check"]
        self.sample = check.sample(self.records, self.seed, c["tokens"],
                                   c["most"])
        self.details = self._gaps(ref, arch) if self.sample else {
            "gap": None}
        self.cell.log(f"check: {self.details}")
        return {"gap": self.details["gap"]}

    def _gaps(self, ref, arch, **stand_in) -> dict:
        tr = self.cell.tr
        prompts = {r.rid: self.reqs[r.rid].tokens for r in self.sample}
        return check.serve_gaps(ref, arch, self.seed, self.sample, prompts,
                                tr["pool"]["cache_len"], tr["output"]["max"],
                                **stand_in)

    def controls(self, ref, arch, lowp, faults: bool = True) -> dict:
        """After ``compare``: the control (the reference in ``lowp`` in the
        program's place) and, with ``faults``, the faults planted in the
        reference in the program's place (every request served under the
        next task's scales) or in what the program produced (one served
        token altered where it is produced)."""
        out = {"control": self._gaps(ref, arch, lowp=lowp)}
        if not faults:
            return out
        count = self.cell.tr["tasks"]["count"]
        out["fault_task"] = self._gaps(
            ref, arch, task_of=lambda r: (r.task + 1) % count)
        longest = self.sample[0]
        served = list(longest.tokens)
        k = len(served) // 2
        served[k] = (served[k] + 1) % self.cell.cfg.vocab_size
        longest.tokens = served
        out["fault_token"] = self._gaps(ref, arch)
        return out
