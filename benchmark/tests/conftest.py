"""Rehearsals of the benchmark on the CPU, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 tests.  Nothing here is a measurement: the
toy cells of ``toy/BENCHMARK.json`` run the real runners at sizes a CPU
holds, with the chip check switched off by the test (``require_chip``).
"""

import argparse
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TOY = os.path.join(HERE, "toy", "BENCHMARK.json")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench():
    import run

    return run


def toy_args(workload: str, *, seed: int = 5, seconds: float = 1.0,
             trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
