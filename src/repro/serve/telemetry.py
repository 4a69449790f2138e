"""Streaming run telemetry: a thin step-metrics sink with a stable schema.

The HomebrewNLP ``wandblog.py`` pattern: benchmarks and the serve loop
don't format or file anything themselves — they push ``(name, value,
unit, extras)`` rows into one ``MetricSink`` as they go, and the sink
serializes everything at the end.  One sink, two consumers:

  * ``benchmarks/kernel_bench.py`` feeds kernel + serving metrics and
    writes ``BENCH_kernels.json`` / ``BENCH_serving.json``;
  * ``repro.serve.driver`` feeds per-run SLO summaries from the traffic
    harness.

Schema (version 1) — what ``benchmarks/trajectory.py`` consumes:

    {"schema": 1,
     "run": {...generating parameters, free-form...},
     "metrics": [{"name": str, "value": number, "unit": str,
                  "wall": bool?,            # wall-clock: machine-dependent,
                                            # excluded from reproducibility
                                            # diffs and trajectory gates
                  "guard": {"direction": "higher"|"lower",
                            "band": float}?,  # trajectory-gated metric:
                                            # fail on a regression beyond
                                            # band (relative)
                  ...extra number/string fields}]}

Wall-clock rows are marked at the CALL SITE (``wall=True``) — the sink
cannot know which numbers are machine-dependent, and an unmarked noisy
metric would flake the trajectory gate.

Spans: ``span(name, **attrs)`` times a block of the program's host code.
Each record is ``(name, id, parent, start_ns, end_ns, attrs)``; the parent
is the enclosing span on the same thread.  Records go into a bounded ring
(``spans()`` reads it), always on; the same call enters
``jax.profiler.TraceAnnotation(name, **attrs)``, so in a profiled run the
span lands in the trace beside the device's operations.  Timestamps are
``time.time_ns()``, the realtime clock the profiler stamps host events
with, so the two copies of a span agree.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import numpy as np

SCHEMA_VERSION = 1
GUARD_DIRECTIONS = ("higher", "lower")


def _jsonable(v):
    """Coerce numpy scalars/bools so json.dump never chokes mid-run."""
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


class MetricSink:
    """Append-only metric stream; optionally echoes rows as they land."""

    def __init__(self, printer: Optional[Callable[[str], None]] = None):
        self._metrics: List[Dict] = []
        self._printer = printer

    def log(self, name: str, value, unit: str = "", *,
            wall: bool = False, guard: Optional[tuple] = None, **extra):
        """Record one metric row.

        ``guard=(direction, band)`` marks the row trajectory-gated:
        ``("higher", 0.15)`` fails CI when the value drops more than 15%
        below the committed baseline (``"lower"``: rises above).
        """
        entry = {"name": str(name), "value": _jsonable(value),
                 "unit": str(unit)}
        if wall:
            entry["wall"] = True
        if guard is not None:
            direction, band = guard
            if direction not in GUARD_DIRECTIONS:
                raise ValueError(f"guard direction {direction!r} "
                                 f"(know: {GUARD_DIRECTIONS})")
            if not 0 <= float(band) < 1:
                raise ValueError(f"guard band {band} must be in [0, 1)")
            if wall:
                # a guarded wall metric must be SELF-NORMALIZED (a ratio
                # of two same-run timings) to survive machine changes —
                # trust the call site, but keep the mark visible
                entry["wall"] = True
            entry["guard"] = {"direction": direction, "band": float(band)}
        for k, v in extra.items():
            entry[k] = _jsonable(v)
        self._metrics.append(entry)
        if self._printer is not None:
            self._printer(f"{name}={entry['value']}{unit and ' ' + unit}")
        return entry

    @property
    def metrics(self) -> List[Dict]:
        return list(self._metrics)

    def payload(self, metrics: Optional[List[Dict]] = None,
                **run_meta) -> Dict:
        """The schema-1 document for (a subset of) the recorded metrics.

        ``run_meta`` must be deterministic for a seeded run — no
        timestamps — so two same-seed runs produce byte-identical files
        modulo wall-marked rows.
        """
        return {"schema": SCHEMA_VERSION,
                "run": {k: _jsonable(v) for k, v in sorted(run_meta.items())},
                "metrics": metrics if metrics is not None else self.metrics}

    def write(self, path: str, metrics: Optional[List[Dict]] = None,
              **run_meta) -> None:
        with open(path, "w") as f:
            json.dump(self.payload(metrics, **run_meta), f, indent=2,
                      sort_keys=True)


def load(path: str) -> Dict:
    """Read a BENCH_*.json document (schema-1 or the pre-schema
    ``{"metrics": [...]}`` layout PR 4 emitted)."""
    with open(path) as f:
        doc = json.load(f)
    if "metrics" not in doc:
        raise ValueError(f"{path}: no 'metrics' key")
    doc.setdefault("schema", 0)
    doc.setdefault("run", {})
    return doc


def stable_metrics(doc: Dict) -> List[Dict]:
    """The machine-independent rows: everything not marked ``wall`` —
    the reproducibility contract ("identical across two seeded runs,
    modulo wall-clock fields") compares exactly this view."""
    return [m for m in doc["metrics"] if not m.get("wall")]


class Span(NamedTuple):
    """One finished span: nanoseconds on ``time.time_ns()``'s clock."""
    name: str
    id: int
    parent: Optional[int]
    start_ns: int
    end_ns: int
    attrs: Dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class _OpenSpan:
    """A span being timed: ``set`` adds attributes known only inside it."""
    __slots__ = ("_rec", "_annotation", "name", "attrs", "id", "parent",
                 "start_ns")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: Dict):
        self._rec, self.name, self.attrs = rec, name, attrs
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._annotation.set_metadata(**attrs)

    def __enter__(self) -> "_OpenSpan":
        stack = self._rec._stack()
        self.id = next(self._rec._ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._annotation.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.time_ns()
        self._annotation.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._ring.append(Span(self.name, self.id, self.parent,
                                    self.start_ns, end, self.attrs))


class SpanRecorder:
    """Bounded in-memory ring of finished spans; the oldest drop first."""

    def __init__(self, maxlen: int = 65536):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._local = threading.local()
        self._ids = itertools.count(1)

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> _OpenSpan:
        """Context manager timing its block as span ``name``."""
        return _OpenSpan(self, name, attrs)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """Recorded spans, oldest first; those named ``name`` if given."""
        return [s for s in list(self._ring) if name is None or s.name == name]


_RECORDER = SpanRecorder()
span = _RECORDER.span
spans = _RECORDER.spans
