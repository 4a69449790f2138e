"""Median host time inside ``Engine.step`` over the window's decode steps:
each step's program span ``engine.step`` less its ``engine.step.wait``
child, the blocking read of the sampled tokens.  The window's steps are the
last ``len(steps)`` ``engine.step`` spans: nothing steps the engine after
the window.  None where the program records no spans."""
from readers import percentile

from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    n = len(ctx["steps"])
    if spans is None or not n:
        return None
    steps = spans("engine.step")[-n:]
    wait = {s.parent: s.duration_ns for s in spans("engine.step.wait")}
    return percentile([1e-6 * (s.duration_ns - wait[s.id]) for s in steps
                       if s.id in wait], 50)
