"""Share of the routed experts that a decode step's MoE layers touch: the
sum of ``experts_hit`` over the sum of ``moe_layers`` times the experts a
layer holds, both attributes of the window's ``engine.step`` program
spans (the last ``len(steps)``).  None where the program records no such
attributes."""
from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    n = len(ctx["steps"])
    if spans is None or not n:
        return None
    steps = [s for s in spans("engine.step")[-n:] if "experts_hit" in s.attrs]
    layers = sum(s.attrs["moe_layers"] for s in steps)
    if not layers:
        return None
    return 100.0 * sum(s.attrs["experts_hit"] for s in steps) / (
        layers * ctx["cfg"].moe.n_experts)
