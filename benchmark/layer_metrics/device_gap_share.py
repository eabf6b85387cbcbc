"""Share of the untraced window in which the host's queue of training steps
had run dry, in %: 1 - the union of the ring's ``device.*`` intervals
(``device.step``: from the step's launch, or the end of the one before, to
when a watcher saw its metrics ready) over the window.  The program's own
reading, in the regime the end-to-end metrics are taken in; the trace's
``device_idle_share.*`` reads the profiler's.  An interval stands for a
step outstanding, not for the device busy: idle time inside a step is not
counted."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _device_gaps import gap_share  # noqa: E402


def read(run: dict):
    return gap_share(run)
