"""95th percentile of the program's ``sched.queue`` span, in ms: from a
request's due time (``Request.arrival_t``) to ``Request.admit_t``, when the
scheduler took it off the queue, over the requests due in the window."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import p95_ms  # noqa: E402


def read(run: dict):
    return p95_ms(run, "sched.queue")
