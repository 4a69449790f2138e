"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the run's context: the window's records (host seconds
from the window's start), the configuration (``cfg``), the chip's peaks,
and, in a traced run, the reduced trace (``trace.reduce``).  A reader that
finds nothing to read returns None, and the metric is left out.
"""
from __future__ import annotations

import numpy as np

import roofline


def percentile(values, q: float):
    """Linear-interpolation percentile (as ``repro.serve.metrics``), or
    None for no values."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(ctx) -> list:
    """Requests due inside the window (not those in flight at its start)."""
    return [r for r in ctx["records"] if 0.0 <= r.due < ctx["window_s"]]


def ttft_ms(ctx, q: float):
    """Every request due in the window, from its due time to its first
    token; one still waiting at the close counts its time so far."""
    end = ctx["window_s"]
    return percentile(
        [1e3 * ((r.first if r.first is not None else end) - r.due)
         for r in due_in_window(ctx)], q)


def tpot_ms(ctx, q: float):
    """Requests completed in the window: (last − first token) over the
    tokens after the first."""
    return percentile([1e3 * (r.last - r.first) / (r.n_out - 1)
                       for r in due_in_window(ctx)
                       if r.done and r.n_out > 1], q)


def out_tok_s(ctx):
    """Every token generated in the window, over the window (``n_out``
    counts a request's tokens from the window's start)."""
    return sum(r.n_out for r in ctx["records"]) / ctx["window_s"]


def queue_wait_ms(ctx, q: float):
    return percentile([1e3 * (r.admit - r.due) for r in due_in_window(ctx)
                       if r.admit is not None], q)


def prefill_ms(ctx, q: float):
    return percentile([1e3 * (a.end - a.start) for a in ctx["admits"]], q)


def decode_ms(ctx, q: float):
    return percentile([1e3 * (s.end - s.start) for s in ctx["steps"]], q)


def installs_per_admit(ctx):
    n = len(ctx["admits"])
    return ctx["installs"] / n if n else None


def prefill_share(ctx):
    return 100.0 * sum(a.end - a.start for a in ctx["admits"]) \
        / ctx["window_s"]


def decode_required_s(ctx, step) -> float:
    f, b = roofline.decode_step(ctx["cfg"], ctx["tr"]["pool"]["slots"],
                                step.live_kv, step.tasks)
    return roofline.seconds(f, b, ctx["peaks"])


def mfu_decode(ctx):
    """Least time of every decode step at the peaks over the steps' measured
    time."""
    steps = ctx["steps"]
    if not steps:
        return None
    need = sum(decode_required_s(ctx, s) for s in steps)
    return 100.0 * need / sum(s.end - s.start for s in steps)


def mfu_tune(ctx):
    cfg, tr = ctx["cfg"], ctx["tr"]
    per_tok = roofline.train_flops_per_token(cfg, tr["seq_len"])
    return 100.0 * per_tok * ctx["tokens"] / ctx["window_s"] \
        / ctx["peaks"]["bf16_flops"]


def idle_share(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_roofline(ctx, kernel: str, m: int, task_rows: float,
                    span: str | None = None):
    """Least time of the kernel's calls in the traced slice (those that
    started in host span ``span``, or all), over their device time.  The
    calls spread evenly over the layers' linears, so a call's need is the
    mean of one pass's."""
    t = ctx["trace"]
    if t is None:
        return None
    spans = [span] if span is not None else list(t["op_s_by_span"])
    secs = sum(t["op_s_by_span"].get(s, {}).get(kernel, 0.0) for s in spans)
    n = sum(t["op_count_by_span"].get(s, {}).get(kernel, 0.0) for s in spans)
    if not secs:
        return None
    calls = roofline.kernel_calls(ctx["cfg"], kernel, m, task_rows)
    per_call = np.mean([roofline.seconds(f, b, ctx["peaks"])
                        for f, b in calls])
    return 100.0 * n * per_call / secs


def mean_task_rows(ctx):
    steps = ctx["steps"]
    return float(np.mean([s.tasks for s in steps])) if steps else 1.0
