"""Tail and rate arithmetic: a stall in a synthetic window moves the tails
and the rate the way it should."""
import pytest

import readers
from serve_loop import Admit, Record, Step


def _window(stall: float):
    """Ten requests due 0.1 s apart; each admitted at once, first token
    0.05 s later, then 9 more tokens 0.02 s apart.  ``stall`` holds request
    5's admission (and every later one) back by that much."""
    recs = []
    for i in range(10):
        due = 0.1 * i
        admit = due + (stall if i >= 5 else 0.0)
        first = admit + 0.05
        recs.append(Record(rid=i, task=0, n_prompt=8, n_new=10, due=due,
                           admit=admit, first=first, last=first + 0.18,
                           n_out=10, done=True))
    return {"records": recs, "window_s": 2.0 + stall, "steps": [],
            "admits": [Admit(r.admit, r.first, 8, 8, False) for r in recs]}


def test_no_stall():
    ctx = _window(0.0)
    assert readers.ttft_ms(ctx, 90) == pytest.approx(50.0)
    assert readers.tpot_ms(ctx, 90) == pytest.approx(20.0)
    assert readers.out_tok_s(ctx) == pytest.approx(100 / 2.0)
    assert readers.queue_wait_ms(ctx, 50) == pytest.approx(0.0)


def test_stall_moves_tail_and_rate():
    ctx = _window(1.0)
    # half the requests waited 1 s more: the tail moves by that, the
    # median of the first half does not, and the same tokens took longer
    assert readers.ttft_ms(ctx, 90) == pytest.approx(1050.0)
    assert readers.ttft_ms(ctx, 40) == pytest.approx(50.0)
    assert readers.tpot_ms(ctx, 90) == pytest.approx(20.0)
    assert readers.out_tok_s(ctx) == pytest.approx(100 / 3.0)


def test_waiting_request_counts_its_time():
    ctx = _window(0.0)
    ctx["records"][-1].first = None
    ctx["records"][-1].done = False
    # due at 0.9 s, still waiting at the 2.0 s close: 1100 ms
    assert readers.ttft_ms(ctx, 100) == pytest.approx(1100.0)


def test_prefill_share_and_decode_median():
    ctx = {"steps": [Step(0, 0.1, 4, 100, 2), Step(0.1, 0.3, 2, 50, 1),
                     Step(0.3, 0.4, 2, 50, 1)],
           "admits": [Admit(0.0, 0.5, 8, 8, False)], "window_s": 2.0}
    assert readers.prefill_share(ctx) == pytest.approx(25.0)
    assert readers.decode_ms(ctx, 50) == pytest.approx(100.0)
    assert readers.mean_task_rows(ctx) == pytest.approx(4 / 3)
