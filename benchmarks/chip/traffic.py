"""The one traffic generator: reads a mix's data file (``traffic/<name>.json``)
and makes its requests or training batches from ``--seed``.

A mix's shape (arrival times, lengths, budgets and the popularity rank of
each request's task, in their order) is drawn from the fixed
``STRUCTURE_SEED``; the run seed fills in the token ids and which task
holds which rank.  So every seed offers the same schedule of work with
other contents, and runs with different seeds differ no more than two runs
of one seed.

A serving mix's ``arrivals.kind`` names the module ``arrivals/<kind>.py``
whose ``schedule(arrivals, rng, seconds)`` says when requests fall due:
``{"due": times, "head": n}`` for an open loop (the first ``head`` are in
flight when the window opens, with a uniform share of their budget left),
or ``{"due": None, "sessions": s, "count": n, "partial": p}`` for a closed
loop (request ``i`` belongs to session ``i % s``; the first ``p`` have a
uniform share of their budget left).

``mode: train`` mixes describe packed documents instead: lengths drawn from
``docs``, joined by ``eos`` and cut into rows of ``seq_len + 1`` tokens.
"""
from __future__ import annotations

import dataclasses
import json
import zlib
from pathlib import Path

import numpy as np

import named

HERE = Path(__file__).resolve().parent
STRUCTURE_SEED = 0


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from ``{"dist": "lognormal", "median", "sigma"}`` or
    ``{"dist": "uniform"}``, clipped to ``[min, max]``."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def task_ranks(rng, spec: dict, n: int) -> np.ndarray:
    """Popularity ranks (0 = hottest) of ``n`` requests: Zipf with exponent
    ``zipf`` over ``count`` tasks (0 = uniform)."""
    count = spec["count"]
    w = 1.0 / np.arange(1, count + 1) ** spec.get("zipf", 0.0)
    return rng.choice(count, size=n, p=w / w.sum())


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray
    n_new: int
    task: int
    due_s: float | None = None       # open loop: due time in the window
    session: int | None = None       # closed loop: owning session
    at_start: bool = False           # open loop: in flight at the window's start


def _rngs(tr: dict, seed: int):
    structure = np.random.default_rng(STRUCTURE_SEED)
    run = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31,
                                 zlib.crc32(json.dumps(tr).encode())])
    return structure, run


def ranked_tasks(tr: dict, seed: int) -> np.ndarray:
    """Task ids by popularity rank (hottest first) for ``seed``."""
    return _rngs(tr, seed)[1].permutation(tr["tasks"]["count"])


def serve_requests(tr: dict, seed: int, seconds: float, vocab: int):
    """Requests of a serving mix.  Open loop: every request due in the
    window, by due time.  Closed loop: ``per_session`` requests for each
    session, in the order each session sends them."""
    structure, run = _rngs(tr, seed)
    plan = named.module("arrivals", tr["arrivals"]["kind"]).schedule(
        tr["arrivals"], structure, seconds)
    due, head = plan["due"], plan.get("head", 0)
    n = plan["count"] if due is None else len(due)
    prompts = lengths(structure, tr["prompt"], n)
    outputs = lengths(structure, tr["output"], n)
    ranks = task_ranks(structure, tr["tasks"], n)
    task_of_rank = run.permutation(tr["tasks"]["count"])
    reqs = []
    # requests in flight at the start are part-way through their budgets:
    # the open loop's head, and each closed-loop session's first request
    part = plan.get("partial", head)
    outputs[:part] = structure.integers(1, outputs[:part] + 1)
    for i in range(n):
        reqs.append(Request(
            rid=i, tokens=run.integers(0, vocab, prompts[i], dtype=np.int32),
            n_new=int(outputs[i]), task=int(task_of_rank[ranks[i]]),
            due_s=None if due is None else float(due[i]),
            session=None if due is not None else i % plan["sessions"],
            at_start=i < head))
    return reqs


def train_tokens(tr: dict, seed: int, vocab: int) -> np.ndarray:
    """(max_steps, batch, seq_len + 1) int32: packed documents, every row
    distinct.  Row ``[i, b]`` gives step ``i``'s tokens ``[:-1]`` and
    labels ``[1:]``."""
    structure, run = _rngs(tr, seed)
    steps, b, s = tr["max_steps"], tr["batch"], tr["seq_len"]
    need = steps * b * (s + 1)
    docs = []
    total = 0
    while total < need:
        n = lengths(structure, tr["docs"], 256)
        docs.extend(n.tolist())
        total += int(n.sum()) + 256
    docs = np.asarray(docs)
    docs = docs[run.permutation(len(docs))]
    stream = run.integers(1, vocab, int(docs.sum()) + len(docs),
                          dtype=np.int32)
    stream[np.cumsum(docs + 1) - 1] = tr["eos"]       # document ends
    return stream[:need].reshape(steps, b, s + 1)
