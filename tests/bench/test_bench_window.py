"""The end-to-end metrics' arithmetic over a window, on hand-made client
records: censored time to first token, gaps still open at the window's
end, rewinds netted out of the token rate, the generator's lag."""
import numpy as np
import pytest

import traffic
import window as W


def rec(i, times=(), due=None, sent=None, done=None, rewinds=(),
        status=None):
    r = W.Record(i, np.zeros(4, np.int32), 8, True, due=due, sent=sent,
                 done=done, status=status)
    r.times = list(times)
    r.tokens = list(range(len(times)))
    r.rewinds = list(rewinds)
    return r


def test_decode_rate_counts_tokens_in_window_net_of_rewinds():
    recs = [rec(0, times=[0.5, 1.5, 2.5, 3.5], rewinds=[(3.0, 2)]),
            rec(1, times=[9.0, 11.0])]
    # window [1, 10]: tokens at 1.5, 2.5, 3.5, 9.0 = 4, minus 2 rewound
    assert W.decode_tok_s(recs, 1.0, 10.0) == pytest.approx(2 / 9)


def test_itl_counts_gaps_ending_in_window_and_open_gaps_at_the_end():
    recs = [rec(0, times=[0.0, 2.0, 3.0], done=3.5),   # finished in window
            rec(1, times=[1.0, 4.0]),                  # open at t1 = 10
            rec(2, times=[-5.0])]                      # open since before t0
    gaps = sorted(W.itl_gaps(recs, 1.0, 10.0))
    # rec0: gaps ending at 2.0 (2.0) and 3.0 (1.0); rec1: 4.0-1.0 = 3.0 and
    # open 10-4 = 6; rec2: open 10-(-5) = 15
    assert gaps == pytest.approx([1.0, 2.0, 3.0, 6.0, 15.0])


def test_ttft_is_from_due_time_and_censored_at_window_end():
    """(``ttft_p95_s``'s arithmetic, for open-loop cells.)"""
    recs = [rec(0, due=1.0, sent=1.5, times=[3.0]),
            rec(1, due=2.0, sent=2.0),                 # no token by t1
            rec(2, due=4.0, sent=4.1, times=[12.0]),   # first token after t1
            rec(3, due=0.5, sent=0.5, times=[1.0])]    # due before t0
    assert sorted(W.ttfts(recs, 1.0, 10.0)) == pytest.approx([2.0, 6.0, 8.0])


def test_percentile_and_attempted_failed():
    assert W.percentile([], 95) is None
    assert W.percentile(list(range(101)), 95) == pytest.approx(95.0)
    recs = [rec(0, due=1.0, status="completed"),
            rec(1, due=2.0, status="quarantined"),
            rec(2, due=20.0),
            rec(3, sent=0.0, done=0.5, status="completed"),   # closed, before
            rec(4, sent=0.0)]                                 # closed, open
    assert W.attempted_failed(recs, 1.0, 10.0, ("completed",)) == (3, 1)


def test_plan_sends_the_same_work_for_every_seed_in_another_order():
    mix = {"loop": "open", "rate_per_s": 2.0, "warm_s": 1.0,
           "prompt": {"dist": "lognormal", "median": 300, "sigma": 1.0,
                      "clip": [128, 4096], "round": "pow2"},
           "output": {"dist": "lognormal", "median": 100, "sigma": 0.8,
                      "clip": [32, 512]},
           "greedy_share": 0.5, "sizes_seed": 3}
    a = traffic.plan(mix, 1, 10.0, 1000)
    b = traffic.plan(mix, 2**31 + 5, 10.0, 1000)
    assert len(a) == len(b) == 22
    key = lambda p: sorted((len(x.prompt), x.n_tokens) for x in p)  # noqa
    assert key(a) == key(b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sum(x.greedy for x in a) == sum(x.greedy for x in b) == 11
    assert all(len(x.prompt) & (len(x.prompt) - 1) == 0 for x in a)
    assert a[0].due_s == 0.0 and all(
        y.due_s >= x.due_s for x, y in zip(a, a[1:]))
