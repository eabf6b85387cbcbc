"""Median self time of the program's ``sched.step`` span, in ms: a scheduler
step less its admit passes, the engine's decode and the retire loop —
the host work between them, and the spans' own cost."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "sched.step", own=True)
