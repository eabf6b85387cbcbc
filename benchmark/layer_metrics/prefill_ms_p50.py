"""Median of the runner's span around ``engine.prefill`` (batch 1, padded
to its bucket, ending with the first token on the host), in ms."""

import statistics


def read(run: dict):
    v = run["window"].get("prefill_ms")
    return statistics.median(v) if v else None
