"""Whole MoE decode step: least time at the peaks (``moe_roofline``: the
experts each step's ``engine.step`` span counts as hit, latent KV at live
lengths) over the measured step time, over every step of the window.
None where the program's spans carry no ``experts_hit``."""
import moe_roofline
import roofline
from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    steps = ctx["steps"]
    if spans is None or not steps:
        return None
    attrs = [s.attrs for s in spans("engine.step")[-len(steps):]]
    if len(attrs) != len(steps) or any("experts_hit" not in a
                                       for a in attrs):
        return None
    m = ctx["tr"]["pool"]["slots"]
    need = sum(roofline.seconds(*moe_roofline.decode_step(
        ctx["cfg"], m, s.live_kv, s.tasks, a["experts_hit"]), ctx["peaks"])
        for s, a in zip(steps, attrs))
    return 100.0 * need / sum(s.end - s.start for s in steps)
