"""The general generator: Poisson arrivals and log-normal sizes drawn
independently, one sample path per mix on a circle as long as the window,
which every seed opens at another point and which has gone round before."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH

VOCAB = 50257


@pytest.fixture(scope="module")
def lg(bench):
    return bench.load_module(os.path.join(BENCH, "loadgen.py"))


def _traffic(name="serve_chat_r80"):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_every_seed_offers_the_same_circle_from_another_point(lg):
    t = _traffic()
    a = lg.schedule(t, 1, 20.0, VOCAB)
    b = lg.schedule(t, 2147483659, 20.0, VOCAB)
    assert len(a) == len(b)
    size = lambda p: (len(p.prompt), p.max_new_tokens)  # noqa: E731
    sa, sb = list(map(size, a)), list(map(size, b))
    assert sa != sb
    turn = next(k for k in range(len(sa)) if sa[k:] + sa[:k] == sb)
    # the same distances between neighbours, the circle's closing one too
    ring = lambda s: np.diff([p.due_s for p in s]  # noqa: E731
                             + [s[0].due_s + 20.0])
    assert np.allclose(np.roll(ring(a), -turn), ring(b))
    gaps = lg.sample_path(t, 20.0)[0]
    closing = 20.0 - gaps.sum() + gaps[0]
    assert np.allclose(sorted(ring(a)), sorted([*gaps[1:], closing]))
    assert all(0.0 <= p.due_s < 20.0 for p in a + b)
    assert [p.due_s for p in a] == sorted(p.due_s for p in a)
    assert lg.schedule(t, 1, 20.0, VOCAB)[5].prompt == a[5].prompt
    assert a[5].prompt != b[(5 - turn) % len(b)].prompt
    lens = [len(p.prompt) for p in a]
    assert 16 <= min(lens) and max(lens) <= 1024
    assert max(max(p.prompt) for p in a) > 0.95 * VOCAB  # the whole vocabulary


@pytest.mark.parametrize("lead_s", [7.5, 20.0, 31.0])
def test_the_lead_in_is_the_window_one_turn_earlier(lg, lead_s):
    t = _traffic()
    win = lg.schedule(t, 77, 20.0, VOCAB)
    led = lg.schedule(t, 77, 20.0, VOCAB, lead_s=lead_s)
    lead, rest = led[:len(led) - len(win)], led[len(led) - len(win):]
    assert [(p.due_s, p.prompt, p.max_new_tokens) for p in rest] == \
        [(p.due_s, p.prompt, p.max_new_tokens) for p in win]
    assert [p.due_s for p in led] == sorted(p.due_s for p in led)
    assert all(-lead_s <= p.due_s < 0.0 for p in lead)
    by_due = {round(p.due_s, 9): p for p in win}
    for p in lead:
        twin = by_due[round(p.due_s % 20.0, 9)]
        assert (len(p.prompt), p.max_new_tokens) == \
            (len(twin.prompt), twin.max_new_tokens)
        assert p.prompt != twin.prompt
    whole, part = divmod(lead_s, 20.0)
    assert len(lead) == int(whole) * len(win) + sum(
        1 for p in win if p.due_s >= 20.0 - part)


def test_arrivals_are_poisson_and_sizes_lognormal(lg):
    """Over a long window: the count is the rate's, the gaps' deviation is
    their mean (an exponential's), counts in stretches of 0.625 s scatter
    as widely as their mean (nothing is evened out), sizes have the mix's
    median, and sizes and gaps are not correlated."""
    t = _traffic()
    rate = t["arrivals"]["rate_rps"]
    gaps, p_len, o_len = lg.sample_path(t, 2000.0)
    n = len(gaps)
    assert abs(n - rate * 2000) < 4 * np.sqrt(rate * 2000)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.05)
    counts = np.histogram(np.cumsum(gaps), bins=3200, range=(0, 2000))[0]
    assert np.var(counts) == pytest.approx(np.mean(counts), rel=0.1)
    assert np.median(p_len) == pytest.approx(t["prompt_len"]["median"],
                                             rel=0.05)
    assert np.median(o_len) == pytest.approx(t["output_len"]["median"],
                                             rel=0.05)
    assert abs(np.corrcoef(gaps, o_len)[0, 1]) < 0.03
    assert abs(np.corrcoef(p_len, o_len)[0, 1]) < 0.03


def test_a_longer_window_extends_a_shorter_one(lg):
    t = _traffic()
    short, long_ = lg.sample_path(t, 12.0), lg.sample_path(t, 23.0)
    for s, l in zip(short, long_):
        assert len(l) > len(s) and np.array_equal(l[:len(s)], s)
