"""The trace reducer: by hand on synthetic events, and on a small trace
recorded on one TPU v5e (``data/small.xplane.pb``: a ``bench.window``
span around two ``serve.decode`` spans, each a jitted matmul chain, with
a host sleep between them)."""
from pathlib import Path

import pytest

import trace

DATA = Path(__file__).resolve().parent / "data"


def test_op_names():
    assert trace.op_name("%quant_gemv.28 = bf16[32,4608] custom-call(…)") \
        == "quant_gemv"
    assert trace.op_name("%while.13 = (s32[]) while(…)") == "while"
    assert trace.op_name("%bitcast_dynamic-update-slice_fusion.4 = bf16[…]") \
        == "bitcast_dynamic-update-slice_fusion"
    assert trace.op_name("%copy.151 = bf16[1]") == "copy"


def test_reduce_by_hand():
    ops = {"/device:TPU:0": [("while", 0, 100), ("quant_gemv", 10, 30),
                             ("fusion", 30, 50), ("quant_gemv", 120, 140),
                             ("fusion", 190, 260)]}
    spans = [("bench.window", 0, 200), ("serve.decode", 0, 60),
             ("serve.admit", 100, 150), ("serve.wait", 150, 185)]
    r = trace.reduce_events(ops, spans)
    # busy: [0, 100] ∪ [120, 140] ∪ [190, 200] inside the window
    assert r["busy_s"] == pytest.approx(130e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["op_s"] == pytest.approx({"quant_gemv": 40e-9,
                                       "fusion": 30e-9})
    assert r["op_s_by_span"]["serve.decode"]["quant_gemv"] == \
        pytest.approx(20e-9)
    assert r["op_count_by_span"]["serve.admit"]["quant_gemv"] == 1
    # idle [100, 120] in admit, [140, 190] centred at 165 in wait
    assert dict((k, v) for k, v in r["idle_gaps"]) == pytest.approx(
        {"serve.admit": 20e-9, "serve.wait": 50e-9})
    assert r["device_ops"][0][0] == "quant_gemv"


def test_recorded_trace():
    ops, spans = trace.load(DATA)
    assert list(ops) == ["/device:TPU:0"]
    names = [n for n, _, _ in spans]
    assert names.count("bench.window") == 1
    assert names.count("serve.decode") == 2
    r = trace.reduce(DATA)
    assert 0 < r["busy_s"] < r["window_s"]
    # the sleep between the two decode spans is the longest idle gap
    assert r["idle_gaps"][0][0] == "serve.wait"
    assert r["idle_gaps"][0][1] > 0.05
    lo, hi = next((s, e) for n, s, e in spans if n == "bench.window")
    busy = trace._clip(trace._union([(s, e) for _, s, e in
                                     ops["/device:TPU:0"]]), lo, hi)
    assert r["busy_s"] == pytest.approx(sum(e - s for s, e in busy) / 1e9)
