"""The control, at a size a test run can hold: the reference computed in
float8 in the program's place fails the cell's limits, on the same
prompts and served tokens or rows, and so do the faults planted in the
reference in the program's place (on the chip they are read at the
cells' own sizes by ``control.py``, through the same ``Job.controls``)."""
import run
import traffic

from test_rehearsal import SEED, small_tune  # noqa: F401

SIZES = dict(n_layers=4, vocab=2048, d_model=512, d_ff=1024, heads=(8, 2))


def controls(job, ctx, ref, arch):
    return job.controls(ref, arch, run.LOWP)


def serve_mix(w: dict) -> dict:
    """The cell's mix cut to this size, with enough served tokens (about
    400, as a chat run compares) for the widest gap to see a flip."""
    tr = traffic.load(w["traffic"])
    return {**tr, **run.sizes_traffic(tr),
            "pool": {"slots": 8, "cache_len": 160},
            "output": {**tr["output"], "min": 40, "max": 120, "median": 80},
            "check": {"tokens": 400, "most": 8}}


def test_serve_control_fails_program_passes():
    w = run.cell("starcoder2-7b-w4.chat")
    r = run.run_cell(w, 11, 6.0, False, sizes=SIZES, tr=serve_mix(w),
                     hook=controls)
    gap, extra = r["check"]["gap"], r["extra"]
    assert gap["value"] <= gap["limit"] < extra["control"]["gap"], r
    assert extra["fault_task"]["gap"] > gap["limit"], extra
    assert extra["fault_token"]["gap"] > gap["limit"], extra


def test_train_control_fails_program_passes(small_tune):  # noqa: F811
    w = run.cell("mistral-7b-w3.tune")
    r = run.run_cell(w, SEED, 1.0, False, sizes=SIZES, hook=controls)
    prog = {k: v["value"] for k, v in r["check"].items()}
    lim = {k: v["limit"] for k, v in r["check"].items()}
    # at this size bfloat16 rounding weighs more than at the cell's (two
    # rows of 64 tokens, vocab 2048), so the program is held only to sit
    # below the control; the program passes at the cell's size on the chip
    assert prog["loss"] < r["extra"]["control"]["loss"], r
    for what in ("control", "fault_half_batch"):
        assert any(r["extra"][what][k] > lim[k] for k in lim), (what, r)
