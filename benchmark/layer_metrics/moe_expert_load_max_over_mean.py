"""The fullest expert's load over the mean load, over all the experts the
router scores (held here or not), in the expert layer where that is
worst: the program's own per-expert counts (``moe.load.<e>``), every step
since the state was made.  1 is balanced; a router whose selection bias
was never trained reads 2 to 3."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe  # noqa: E402


def read(run: dict):
    c = _afmoe.moe_counters()
    load = [v for k, v in c.items() if k.startswith("moe.load.")]
    if not load or sum(load) <= 0:
        return None
    return max(load) * len(load) / sum(load)
