"""Reduction of a profiler trace (``*.xplane.pb``) to the numbers the
per-layer readers and the result's ``breakdown`` take.

The traced slice is the host span ``bench.window``.  Device time is read
from the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane: busy is the
union of the intervals in which an operation ran (a ``while`` op and the
body ops inside it overlap, and the union counts them once).  An operation
is named by its HLO instruction with the numeric suffix dropped
(``%quant_gemv.28 = …`` is ``quant_gemv``), so kernels are found by the
names their ``pallas_call`` gives them.  Each idle gap inside the slice is
named by the benchmark's host span (``serve.decode``, ``train.wait``, …)
that covers its middle, or ``host`` where none does.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

CONTAINERS = ("while", "conditional", "call")
_NAME = re.compile(r"^%?([A-Za-z_\-]+?)(?:\.\d+)*(?:\s|=|$)")


def op_name(hlo: str) -> str:
    m = _NAME.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def reduce_events(device_ops: dict, host_spans: list) -> dict:
    """``device_ops``: {device: [(name, start_ns, end_ns)]}; ``host_spans``:
    [(name, start_ns, end_ns)] with one ``bench.window``.  Returns busy and
    window seconds (busy averaged over devices), device time and count by
    op name (containers left out), device time by the host span an op
    started in, idle seconds by host span, and the top ten of each list."""
    win = [(s, e) for n, s, e in host_spans if n == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0]
    spans = sorted((s, e, n) for n, s, e in host_spans
                   if n != "bench.window")
    starts = [s for s, _, _ in spans]
    busy_total, by_op, idle = 0.0, collections.Counter(), collections.Counter()
    n_ops = collections.Counter()
    by_span = collections.defaultdict(collections.Counter)
    n_by_span = collections.defaultdict(collections.Counter)

    def span_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and spans[i][1] >= t else "host"

    for dev, ops in device_ops.items():
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        busy = _clip(_union([(s, e) for _, s, e in inside]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in inside:
            if n not in CONTAINERS:
                d = min(e, hi) - max(s, lo)
                by_op[n] += d
                n_ops[n] += 1
                sp = span_at(s)
                by_span[sp][n] += d
                n_by_span[sp][n] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            idle[span_at((a + b) / 2)] += b - a
    ndev = max(len(device_ops), 1)
    top = lambda c: [[k, v / 1e9 / ndev] for k, v in c.most_common(10)]
    return {"busy_s": busy_total / 1e9 / ndev, "window_s": (hi - lo) / 1e9,
            "op_s": {k: v / 1e9 / ndev for k, v in by_op.items()},
            "op_count": {k: v / ndev for k, v in n_ops.items()},
            "op_s_by_span": {sp: {k: v / 1e9 / ndev for k, v in c.items()}
                             for sp, c in by_span.items()},
            "op_count_by_span": {sp: {k: v / ndev for k, v in c.items()}
                                 for sp, c in n_by_span.items()},
            "device_ops": top(by_op), "idle_gaps": top(idle)}


def load(path: Path):
    """(device_ops, host_spans) of the newest ``*.xplane.pb`` under
    ``path``."""
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    pd = ProfileData.from_file(str(files[-1]))
    device_ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (op_name(ev.name), ev.start_ns, ev.end_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", "serve.", "train.")):
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    return device_ops, host


def reduce(path: Path) -> dict:
    return reduce_events(*load(path))
