"""Median of the program's ``engine.decode.dispatch`` span, in ms: until
the decode executable's call (and the token column's slice) returns."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "engine.decode.dispatch")
