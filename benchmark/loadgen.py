"""The one general traffic generator: a schedule of requests from a traffic
file's parameters and a seed.

Arrivals are a Poisson process: the gaps between requests are drawn
independently from the exponential distribution at ``rate_rps``, and each
request's prompt and output lengths independently from the mix's
log-normals (``median``, ``sigma``, clipped to ``min``..``max``).  Nothing
is evened out: a window holds the bursts and the lulls that the draw
holds, and as many requests as fall into it.

The draw is the mix's own (``trace_seed`` in the traffic file), one sample
path that a longer window extends.  ``--seed`` does not draw another.  The
window's stretch of the path is laid on a circle as long as the window,
and the seed picks the point of the circle at which the window opens: the
same requests at the same distances from each other, in the same cyclic
order, from another start.  It also draws the token ids.  Before the
window opens the same circle has gone round already: the requests of the
``lead_s`` seconds before the opening are the window's own, one turn
earlier.  So the system meets the window in the state in which this
traffic leaves it, whichever point the seed picked, every seed offers the
same work, and runs with different seeds differ by what the system does,
not by what it was sent or by which long answers the close cuts off.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Planned:
    due_s: float          # seconds after the schedule's start
    prompt: list
    max_new_tokens: int


def lognormal(dist: dict, rng, n: int) -> np.ndarray:
    x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def sample_path(traffic: dict, seconds: float):
    """``(gaps, prompt_len, output_len)`` of the requests of the mix's
    sample path that are due inside ``[0, seconds)``: the first arrives one
    gap after the start.  Gaps and sizes come from streams of their own,
    so a longer window holds a shorter one's requests and more."""
    rate = float(traffic["arrivals"]["rate_rps"])
    stream = lambda k: np.random.default_rng(  # noqa: E731
        [int(traffic["trace_seed"]), 0, k])
    rng_gap, block = stream(0), max(int(2 * rate * seconds) + 16, 16)
    gaps = rng_gap.standard_exponential(block)
    while gaps.sum() / rate < seconds:
        gaps = np.concatenate([gaps, rng_gap.standard_exponential(block)])
    gaps = gaps / rate
    n = max(int(np.searchsorted(np.cumsum(gaps), seconds, side="left")), 1)
    return (gaps[:n], lognormal(traffic["prompt_len"], stream(1), n),
            lognormal(traffic["output_len"], stream(2), n))


def schedule(traffic: dict, seed: int, seconds: float, vocab_size: int,
             *, lead_s: float = 0.0) -> list[Planned]:
    """The requests due inside ``[-lead_s, seconds)``, in the order they
    are due; the window opens at 0."""
    gaps, p_len, o_len = sample_path(traffic, seconds)
    # (a window too short for the path's first gap still gets one request)
    at = np.minimum(np.cumsum(gaps), seconds * (1.0 - 1e-9))
    rng = np.random.default_rng(int(seed))
    due = np.mod(at - rng.uniform(0.0, seconds), seconds)
    order = np.argsort(due, kind="stable")
    due, p_len, o_len = due[order], p_len[order], o_len[order]

    def requests(when):
        return [Planned(float(d), [int(t) for t in rng.integers(
            0, vocab_size, size=int(p_len[i]))], int(o_len[i]))
            for i, d in enumerate(when) if d >= -lead_s]

    # the window's ids are drawn first, so that they do not hang on lead_s
    window = requests(due)
    turns = range(int(np.ceil(lead_s / seconds)), 0, -1)
    return [r for k in turns for r in requests(due - k * seconds)] + window
