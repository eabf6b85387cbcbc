"""What the readers of the device's gaps share.  The program's ring
(``tpuframe.obs.timeline``) holds ``device.<name>`` intervals on its own
clock, on the lane ``"device"``: from a training step's launch (or the
end of the interval before) to when a watcher saw one of its outputs
ready.  A gap is time inside the window that no such interval covers:
time in which the host's queue of steps had run dry.  Idle time of the
device inside an outstanding step is not seen.  The window is
``_program_spans.ring_window``'s: the untraced part of a ``--trace 1``
run.  Where the ring has no ``device.*`` record (a commit from before the
device's intervals) every reader finds nothing."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import ring_window  # noqa: E402

DEVICE_PREFIX = "device."


def _union(intervals, t0: float, t1: float) -> list:
    merged: list = []
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in intervals
                       if b > t0 and a < t1):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def gap_share(run: dict):
    """The part of the window that no ``device.*`` interval covers, in %;
    None where there is nothing to read."""
    found = ring_window(run)
    if found is None:
        return None
    timeline, t0, t1 = found
    ring = timeline.spans()
    device = [(s.t0, s.t1) for s in ring if s.name.startswith(DEVICE_PREFIX)]
    if not device:
        return None
    # a ring that has dropped its oldest records covers only what follows
    t0 = min(max(t0, ring[0].t1), t1)
    if t1 <= t0:
        return None
    busy = sum(b - a for a, b in _union(device, t0, t1))
    return 100.0 * (1.0 - busy / (t1 - t0))
