"""The harness's window loop end to end at a tiny size on the CPU, with the
Pallas kernels in interpret mode: set-up, window, metric readers and the
comparison with the reference.  The rehearsal entry (``sizes=``) reports
no device metric, and ``run.py`` itself refuses to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import run
import traffic

ROOT = Path(__file__).resolve().parents[3]
SIZES = dict(n_layers=2, vocab=512, d_model=256, d_ff=512, heads=(4, 2))
SEED = 2 ** 31 + 77


@pytest.fixture
def small_tune(monkeypatch):
    real = traffic.load

    def load(name):
        tr = real(name)
        if tr["mode"] == "train":
            tr = {**tr, "batch": 2, "seq_len": 64, "max_steps": 8}
        return tr
    monkeypatch.setattr(traffic, "load", load)


def _e2e(cell):
    return {m["name"] for m in run.metric_names(cell, trace=False)}


def test_serve_cell():
    cell = "starcoder2-7b-w4.chat"
    r = run.run_cell(run.cell(cell), SEED, 3.0, False, sizes=SIZES)
    assert r["correct"], r["check"]
    assert set(r["metrics"]) <= _e2e(cell) and "setup_s" in r["metrics"]
    assert "out_tok_s" in r["metrics"] and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert "busy_s" not in r["device"] and "breakdown" not in r
    assert list(r)[-1] == "check"


def test_closed_loop_mix():
    """The closed-loop mix (``traffic/long.json``, kept for the long cell
    the chip budget left out) through the same harness."""
    w = {"name": "rehearsal.long", "config": "starcoder2-7b-w4",
         "traffic": "long", "chips": 1}
    r = run.run_cell(w, SEED, 3.0, False, sizes=SIZES)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and "setup_s" in r["metrics"]


def test_tune_cell(small_tune):
    cell = "mistral-7b-w3.tune"
    r = run.run_cell(run.cell(cell), SEED, 2.0, False, sizes=SIZES)
    # the limits are the cell's, set on the chip at its own size; at this
    # size bfloat16 rounding weighs more, so only the readings are checked
    assert set(r["check"]) == {"loss", "grad", "change"}
    assert all(0 <= v["value"] < 0.05 for v in r["check"].values()), r
    assert set(r["metrics"]) == _e2e(cell)
    assert r["metrics"]["tune_tok_s"]["value"] > 0


def test_run_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "starcoder2-7b-w4.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
