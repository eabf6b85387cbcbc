"""What the ``afmoe`` cells' readers share: the counts module beside
``flops.py``, the program's routing counters, and device time by op name.

A traced run's ``device_ops`` lists its ten longest ops as
``<category>:<name>``; the categories tell Mosaic calls apart by their
results and cannot tell a grouped expert product from ``flash_bwd_dq``
(both are Mosaic calls with one result), so the flash reader goes by the
name after the colon, and the grouped products, which at one chip's share
are too short to be among the ten, are what is left of their category
once the ops of other names among the ten are taken out."""

from __future__ import annotations

import importlib.util
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))


def counts():
    """``benchmark/flops_trinity_mini.py`` as a module."""
    name = "bench_flops_trinity_mini"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(_HERE),
                               "flops_trinity_mini.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def moe_counters() -> dict:
    """The program's ``moe.*`` counters, fetched from the device now; empty
    where the program has none (a parent commit, another model)."""
    try:
        from tpuframe.obs import metrics
    except ImportError:
        return {}
    return metrics.counters("moe.")


def rows_here_per_layer_step(run: dict):
    """Rows an expert layer computed here a step, from the counters: every
    step since the state was made counts alike (the traffic is one
    stream).  None without counters."""
    c = moe_counters()
    arch = run["config"]["arch"]
    picks = run["traffic"]["flops_args"]["seq_len"] \
        * run["traffic"]["job"]["global_batch"] // run["window"]["chips"] \
        * arch["num_experts_per_tok"]
    if not c.get("moe.tokens_routed") or not c.get("moe.layers"):
        return None
    layer_steps = c["moe.tokens_routed"] / picks
    return c["moe.rows_here"] / layer_steps


def device_seconds(run: dict, names: tuple) -> float:
    """Device time in the traced steps of the ops called ``names``."""
    ops = (run.get("trace") or {}).get("device_ops") or []
    return sum(secs for key, secs in ops
               if key.split(":", 1)[-1] in names)


def grouped_product_seconds(run: dict) -> float:
    """Device time in the traced steps of the Mosaic calls with one result
    (the category the benchmark calls ``flash_bwd_dq``) that are not among
    the ten longest ops under another name: ``moe_gmm``, ``moe_gmm_dx`` and
    ``moe_gmm_dw``, and, should it ever drop out of the ten, the flash dq
    kernel too (the share then reads low, never high)."""
    trace = run.get("trace") or {}
    whole = (trace.get("by_category_s") or {}).get("flash_bwd_dq", 0.0)
    others = sum(secs for key, secs in trace.get("device_ops") or []
                 if key.startswith("flash_bwd_dq:")
                 and not key.split(":", 1)[1].startswith("moe_gmm"))
    return max(whole - others, 0.0)
