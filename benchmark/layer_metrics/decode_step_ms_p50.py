"""Median of the runner's span around ``engine.decode_step`` (all slots,
ending with the tokens on the host), in ms."""

import statistics


def read(run: dict):
    v = run["window"].get("decode_step_ms")
    return statistics.median(v) if v else None
