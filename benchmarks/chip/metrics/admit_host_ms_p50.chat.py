"""Median host time inside ``Engine.admit`` over the requests admitted in
the window: each request's program span ``engine.admit`` (found by its
``rid``; warm-up admits carry none) less its ``engine.admit.wait`` child,
the blocking first-token read that waits for the prefill.  None where the
program records no spans."""
from readers import percentile

from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    if spans is None:
        return None
    rids = {r.rid for r in ctx["records"]
            if r.due >= 0 and r.admit is not None}
    admits = {}
    for s in spans("engine.admit"):      # a rid's newest span is this run's
        if s.attrs.get("rid") in rids:
            admits[s.attrs["rid"]] = s
    wait = {s.parent: s.duration_ns for s in spans("engine.admit.wait")}
    return percentile([1e-6 * (s.duration_ns - wait[s.id])
                       for s in admits.values() if s.id in wait], 50)
