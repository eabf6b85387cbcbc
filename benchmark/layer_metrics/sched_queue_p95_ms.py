"""95th percentile of the time a request waited between being due and its
admission (the start of its prefill), in ms, over the requests due in the
measured part of the window.  The scheduler admits in the order of
submission, so the runner pairs its k-th prefill span with the k-th
request sent."""

import math


def read(run: dict):
    q = run["window"].get("queue_ms")
    if not q:
        return None
    s = sorted(q)
    return s[min(max(math.ceil(0.95 * len(s)) - 1, 0), len(s) - 1)]
