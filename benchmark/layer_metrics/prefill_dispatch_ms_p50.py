"""Median of the program's ``engine.prefill.dispatch`` span, in ms: until
the bucket's executable call returns, before the first token is waited
for."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _program_spans import median_ms  # noqa: E402


def read(run: dict):
    return median_ms(run, "engine.prefill.dispatch")
