"""``ServeConfig`` — the one serving-policy surface.

Replaces the positional/keyword sprawl of the old
``Engine.serve(requests, n_slots, cache_len, *, scheduler, resident_tasks)``
entry point with a single validated dataclass, and carries the admission-
control knobs the production harness adds (bounded wait queue, deadline
shedding) plus the virtual clock that makes SLO metrics deterministic on a
simulation host (docs/SERVING.md "clocks").
"""
from __future__ import annotations

import dataclasses
from typing import Optional

SCHEDULERS = ("auto", "resident", "drain")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Policy for one ``Engine.serve`` run.

    Pool shape:
      * ``n_slots`` — paged KV slots decoded per step (one compiled shape).
      * ``cache_len`` — KV capacity per slot; ``None`` sizes it to the
        longest request (prompt + budget).

    Mixed-task policy (``scheduler``): ``"drain"`` | ``"resident"`` |
    ``"auto"`` — semantics in ``Engine.serve``'s docstring.

    Admission control (overload degrades gracefully instead of queueing
    unboundedly — every outcome is accounted in ``ServeReport``):
      * ``queue_bound`` — max requests WAITING for a slot.  Arrivals that
        would leave the wait queue deeper than this are **rejected** at
        arrival (newest first — FIFO fairness for earlier arrivals).
        ``None`` = unbounded (the pre-harness behavior).
      * ``shed_after_s`` — queue-wait deadline: a request still waiting
        after this many (virtual) seconds is **shed** at its next
        admission consideration.  ``None`` = never shed.

    Virtual clock (deterministic SLO accounting):
      * ``step_s`` — virtual seconds one pool decode step costs.
      * ``prefill_s`` — virtual seconds one admit (prefill) costs;
        ``None`` = same as ``step_s``.
    Wall-clock arrivals (``Request.arrival_s``) are compared against this
    clock; step-clock arrivals (``arrival_step``) gate on pool steps
    directly, so pre-harness workloads replay bit-identically.

    Tiered ScaleBank (docs/SERVING.md "Tiered ScaleBank"):
      * ``prefetch_depth`` — how many distinct upcoming tasks the serve
        loop warms ahead of admission each iteration (wait queue first,
        then pending arrivals).  0 disables prefetch; every cold task
        then pays its full tier costs at admit.
      * ``host_cache_tasks`` — tier-1 capacity applied to the engine's
        bank for the run (LRU over deserialized scale sets); ``None``
        leaves the bank's own bound untouched.
      * ``disk_load_s`` — virtual seconds one tier-2→tier-1 npz load
        costs (loads serialize on one virtual disk lane).
      * ``install_s`` — virtual seconds one tier-1→tier-0 install costs
        (resident row write, or the drain path's scale swap).
    Both costs default to 0 so pre-tiering workloads replay
    bit-identically; the serve loop charges only the remainder a prefetch
    failed to hide (``RequestMetrics.swap_wait_s``).
    """
    n_slots: int = 4
    cache_len: Optional[int] = None
    scheduler: str = "auto"
    resident_tasks: int = 4
    queue_bound: Optional[int] = None
    shed_after_s: Optional[float] = None
    step_s: float = 1.0
    prefill_s: Optional[float] = None
    prefetch_depth: int = 2
    host_cache_tasks: Optional[int] = None
    disk_load_s: float = 0.0
    install_s: float = 0.0
    # round admitted prompts up to power-of-two lengths (masked padding):
    # a mixed trace compiles O(log max_len) prefill variants instead of one
    # per distinct length.  Token streams are unchanged — padded rows are
    # causally invisible and the head gathers the last REAL row.  Ignored
    # (always off) for families the registry marks non-bucketable
    # (recurrent state) and under sliding-window ring caches.
    bucket_prompts: bool = True

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError(f"n_slots={self.n_slots} must be >= 1")
        if self.cache_len is not None and self.cache_len < 1:
            raise ValueError(f"cache_len={self.cache_len} must be >= 1")
        if self.scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {self.scheduler!r} "
                             f"(know: {', '.join(SCHEDULERS)})")
        if self.resident_tasks < 1:
            raise ValueError(
                f"resident_tasks={self.resident_tasks} must be >= 1")
        if self.queue_bound is not None and self.queue_bound < 0:
            raise ValueError(f"queue_bound={self.queue_bound} must be >= 0")
        if self.shed_after_s is not None and self.shed_after_s < 0:
            raise ValueError(
                f"shed_after_s={self.shed_after_s} must be >= 0")
        if self.step_s <= 0:
            raise ValueError(f"step_s={self.step_s} must be > 0")
        if self.prefill_s is not None and self.prefill_s < 0:
            raise ValueError(f"prefill_s={self.prefill_s} must be >= 0")
        if self.prefetch_depth < 0:
            raise ValueError(
                f"prefetch_depth={self.prefetch_depth} must be >= 0")
        if self.host_cache_tasks is not None and self.host_cache_tasks < 1:
            raise ValueError(
                f"host_cache_tasks={self.host_cache_tasks} must be >= 1")
        if self.disk_load_s < 0:
            raise ValueError(f"disk_load_s={self.disk_load_s} must be >= 0")
        if self.install_s < 0:
            raise ValueError(f"install_s={self.install_s} must be >= 0")

    @property
    def admit_cost_s(self) -> float:
        return self.step_s if self.prefill_s is None else self.prefill_s
