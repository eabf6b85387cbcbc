"""What the readers of the program's own spans share.  The spans are
``tpuframe.obs.timeline``'s: made inside the program where the work
happens, kept in its process-wide ring on ``time.monotonic`` (the runners'
clock) and read here, in the same process, once the run is over.  Like
the metrics beside them, these read the measured, untraced part of the
window: a serving window from ``opened_at``, a training window from its
first ``data_wait``, for ``wall_s``.  Where the program has no such ring
(a commit before PR 25) every reader finds nothing."""

import math
import statistics


def ring_window(run: dict):
    """``(timeline, t0, t1)``, or None where there is nothing to read."""
    try:
        from tpuframe.obs import timeline
    except ImportError:
        return None
    w = run["window"]
    if w.get("kind") == "serve":
        t0 = w.get("opened_at")
    else:
        waits = w.get("spans", {}).get("data_wait")
        t0 = waits[0][0] if waits else None
    if not hasattr(timeline, "self_ms") or t0 is None or not w.get("wall_s"):
        return None
    return timeline, t0, t0 + w["wall_s"]


def _durations_ms(run: dict, name: str, own: bool = False) -> list:
    found = ring_window(run)
    if found is None:
        return []
    timeline, t0, t1 = found
    return (timeline.self_ms if own else timeline.durations_ms)(name, t0, t1)


def median_ms(run: dict, name: str, *, own: bool = False):
    """Median duration (``own``: self time) of the spans called ``name``
    that started in the window, in ms."""
    values = _durations_ms(run, name, own)
    return statistics.median(values) if values else None


def p95_ms(run: dict, name: str):
    """Nearest-rank 95th percentile, as the runners take theirs."""
    s = sorted(_durations_ms(run, name))
    return s[max(math.ceil(0.95 * len(s)) - 1, 0)] if s else None


def share_of_window(run: dict, name: str):
    """The part of the window that spans called ``name`` cover, in %."""
    found = ring_window(run)
    if found is None:
        return None
    timeline, t0, t1 = found
    inside = sum(max(min(s.t1, t1) - max(s.t0, t0), 0.0)
                 for s in timeline.spans(name, t1=t1))
    return 100.0 * inside / (t1 - t0)
