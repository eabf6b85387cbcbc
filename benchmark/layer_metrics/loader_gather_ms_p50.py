"""Median of the program's ``loader.gather`` span, in ms: the prefetch
worker's ``dataset[idx]``, one batch of rows gathered on the host."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "loader.gather")
