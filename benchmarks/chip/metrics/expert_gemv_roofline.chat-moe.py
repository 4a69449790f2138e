"""Grouped expert kernel (``quant_gmm``) in the decode steps of the traced
slice: least time at the peaks over device time.  A call's need is the
mean over the expert MLP's three linears of ``kernels/quant_gmm.py`` at the
window's mean experts hit a layer (the ``engine.step`` spans' counters)
and mean tasks a step.  None where the trace has no such call or the spans
no such counter."""
import numpy as np

import moe_roofline
import roofline
from readers import mean_task_rows
from repro.serve import telemetry


def read(ctx):
    t, spans = ctx["trace"], getattr(telemetry, "spans", None)
    n_steps = len(ctx["steps"])
    if t is None or spans is None or not n_steps:
        return None
    secs = t["op_s_by_span"].get("serve.decode", {}).get("quant_gmm", 0.0)
    calls = t["op_count_by_span"].get("serve.decode", {}).get("quant_gmm", 0)
    attrs = [s.attrs for s in spans("engine.step")[-n_steps:]
             if "experts_hit" in s.attrs]
    layers = sum(a["moe_layers"] for a in attrs)
    if not secs or not layers:
        return None
    cfg, q = ctx["cfg"], ctx["cfg"].quant
    hit = sum(a["experts_hit"] for a in attrs) / layers
    rows = ctx["tr"]["pool"]["slots"] * cfg.moe.top_k
    cost = roofline.kernel("quant_gmm").cost
    per_call = np.mean([roofline.seconds(*cost(
        rows, n, k, q.bits, q.layout, roofline.groups(cfg, k),
        mean_task_rows(ctx), hit), ctx["peaks"])
        for n, k in moe_roofline.gated(cfg.moe.d_ff_expert or cfg.d_ff,
                                       cfg.d_model)])
    return 100.0 * calls * per_call / secs
