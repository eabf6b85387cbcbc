"""Median of the program's ``loader.put`` span, in ms: the host side of the
prefetch worker's ``device_put``s of one batch (they return before the
transfer ends; nothing waits for it)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "loader.put")
