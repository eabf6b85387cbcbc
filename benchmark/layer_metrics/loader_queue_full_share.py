"""Share of the window in which the prefetch worker stood in the program's
``loader.queue_full`` span, in %: a batch was ready and the training loop
had not asked for it.  0 means the loader sets the pace, near 100 that
the step does."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import share_of_window  # noqa: E402


def read(run: dict):
    return share_of_window(run, "loader.queue_full")
