"""The whole step's share of the chip's bf16 peak for a ``deepseek_v3``
cell, in %: the operations the forward and backward passes need per
sequence (``flops_kanana2.py``: no recomputation, the attention's causal
pairs at 192 + 128 wide, and the routed experts' rows as the program's
counters read them) times sequences per second per chip, over the peak.
From the untraced part of the window."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _afmoe  # noqa: E402
import flops_kanana2  # noqa: E402


def read(run: dict):
    w, peaks = run["window"], run["peaks"]
    if w.get("kind") != "train" or peaks is None:
        return None
    rows = _afmoe.rows_here_per_layer_step(run)
    if rows is None:
        return None
    per_example = flops_kanana2.deepseek_v3_train_flops_per_sequence(
        run["config"]["arch"], rows_here=rows,
        **run["traffic"].get("flops_args", {}))
    return 100.0 * per_example * w["examples_per_s_per_chip"] \
        / peaks["bf16_flops_per_s"]
