"""Median of the program's ``engine.decode.fetch`` span, in ms: the wait
for the step's tokens on the host (``np.asarray``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "engine.decode.fetch")
