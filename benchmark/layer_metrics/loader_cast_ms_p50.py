"""Median of the program's ``loader.cast`` span, in ms: the prefetch
worker's host ``astype`` of one batch's float inputs to the compute dtype."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "loader.cast")
