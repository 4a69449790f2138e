"""The program's own spans (``repro.serve.telemetry``) as the chat cell's
readers see them, in a ``ServeLoop`` rehearsal at ``test_rehearsal.py``'s
sizes: the span names stay clear of the prefixes the trace reduction keeps,
each of the four span readers reads a finite value, and each window
``engine.step`` lies inside the benchmark's own ``Step``."""
import math

import pytest

import run
from test_rehearsal import SEED, SIZES

from repro.serve import telemetry

CELL = "starcoder2-7b-w4.chat"
READERS = ("decode_host_ms_p50.chat", "admit_host_ms_p50.chat",
           "install_ms_p50.chat", "kv_live_share.chat")
# host events ``trace.load`` keeps: a program span under one of these would
# take device time away from the benchmark's own span around it
TRACE_PREFIXES = ("bench.", "serve.", "train.")


@pytest.fixture(scope="module")
def window():
    """The rehearsal's window context and every span the program recorded
    in the run."""
    before = max((s.id for s in telemetry.spans()), default=0)
    seen = {}

    def hook(job, ctx, ref, arch):
        seen["ctx"] = ctx
    r = run.run_cell(run.cell(CELL), SEED, 6.0, False, sizes=SIZES,
                     hook=hook)
    assert r["correct"], r["check"]
    return seen["ctx"], [s for s in telemetry.spans() if s.id > before]


def test_span_names_clear_trace_prefixes(window):
    _, spans = window
    names = {s.name for s in spans}
    assert names == {"engine.step", "engine.step.wait", "engine.admit",
                     "engine.admit.wait", "stack.install"}
    assert not [n for n in names if n.startswith(TRACE_PREFIXES)]


@pytest.mark.parametrize("name", READERS)
def test_reader_is_finite(window, name):
    ctx, _ = window
    assert ctx["installs"] > 0 and ctx["steps"] and ctx["admits"]
    value = run.reader(name)(ctx)
    assert value is not None and math.isfinite(value) and value >= 0
    if name == "kv_live_share.chat":
        assert 0 < value <= 100


def test_step_span_inside_benchmark_step(window):
    ctx, _ = window
    steps = ctx["steps"]
    spans = telemetry.spans("engine.step")[-len(steps):]
    assert len(spans) == len(steps)
    for span, step in zip(spans, steps):
        assert span.duration_ns / 1e9 <= step.end - step.start
        assert span.attrs["active"] == step.active
        assert span.attrs["kv_rows_live"] == step.live_kv
