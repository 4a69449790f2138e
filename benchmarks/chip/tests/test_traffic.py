"""The traffic generator repeats exactly from a seed, and every seed offers
the same schedule of work with other contents."""
import numpy as np
import pytest

import traffic


def _serve(name, seed, seconds=30.0):
    return traffic.serve_requests(traffic.load(name), seed, seconds, 49152)


def test_serve_repeats_from_seed():
    for name in ("chat", "long"):
        a, b = _serve(name, 2 ** 31 + 5), _serve(name, 2 ** 31 + 5)
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            assert (x.due_s, x.n_new, x.task, x.session) == \
                (y.due_s, y.n_new, y.task, y.session)
            np.testing.assert_array_equal(x.tokens, y.tokens)


def test_seeds_share_schedule_not_contents():
    a, b = _serve("chat", 11), _serve("chat", 12)
    assert [(r.due_s, len(r.tokens), r.n_new) for r in a] == \
        [(r.due_s, len(r.tokens), r.n_new) for r in b]
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert [r.task for r in a] != [r.task for r in b]


def test_chat_shape():
    tr = traffic.load("chat")
    reqs = _serve("chat", 3, seconds=51.0)
    head = tr["arrivals"]["in_flight"]
    assert len(reqs) == head + round(tr["arrivals"]["rate_per_s"] * 51.0)
    assert all(r.at_start for r in reqs[:head])
    assert not any(r.at_start for r in reqs[head:])
    reqs = reqs[head:]
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 51.0
    for r in reqs:
        assert tr["prompt"]["min"] <= len(r.tokens) <= tr["prompt"]["max"]
        assert tr["output"]["min"] <= r.n_new <= tr["output"]["max"]
        assert len(r.tokens) + r.n_new - 1 <= tr["pool"]["cache_len"]


def test_long_sessions_fit_and_spread():
    tr = traffic.load("long")
    reqs = _serve("long", 4)
    s = tr["arrivals"]["sessions"]
    assert len(reqs) == s * tr["arrivals"]["per_session"]
    assert all(len(r.tokens) + r.n_new - 1 <= tr["pool"]["cache_len"]
               for r in reqs)
    first = [r.n_new for r in reqs[:s]]
    assert min(first) < tr["output"]["min"]     # budgets part-way through


def test_train_tokens_repeat_and_differ():
    tr = {**traffic.load("tune"), "max_steps": 4, "seq_len": 128}
    a = traffic.train_tokens(tr, 9, 32768)
    np.testing.assert_array_equal(a, traffic.train_tokens(tr, 9, 32768))
    assert a.shape == (4, tr["batch"], 129)
    rows = a.reshape(-1, 129)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert (a == tr["eos"]).any()


def test_gamma_arrivals_are_bursty_and_shared_by_seeds():
    """``arrivals/gamma.py``: a bursty open loop from data alone."""
    tr = {**traffic.load("chat"), "arrivals": {
        "kind": "gamma", "rate_per_s": 1.3, "cv": 3.0, "in_flight": 4}}
    a = traffic.serve_requests(tr, 5, 400.0, 49152)
    b = traffic.serve_requests(tr, 6, 400.0, 49152)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(r.at_start and r.due_s == 0.0 for r in a[:4])
    due = np.asarray([r.due_s for r in a[4:]])
    assert (np.diff(due) >= 0).all() and due[-1] < 400.0
    assert 0.5 < len(due) / (1.3 * 400.0) < 1.5
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 2.0        # bursts, not Poisson's 1


def test_unknown_arrivals_kind_names_its_file():
    tr = {**traffic.load("chat"), "arrivals": {"kind": "nosuch"}}
    with pytest.raises(FileNotFoundError, match="arrivals/nosuch.py"):
        traffic.serve_requests(tr, 1, 10.0, 512)
