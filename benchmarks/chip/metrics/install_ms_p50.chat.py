"""Median host time of a resident-stack row install in the window
(``ResidentStack.ensure``'s miss path: the host copy of the task's scales,
their upload and the donated row write), from the last ``installs``
``stack.install`` program spans.  None where the window installed nothing
or the program records no spans."""
from readers import percentile

from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    n = ctx["installs"]
    if spans is None or not n:
        return None
    return percentile([1e-6 * s.duration_ns
                       for s in spans("stack.install")[-n:]], 50)
