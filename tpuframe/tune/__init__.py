"""tpuframe.tune — offline AOT autotuning (PERF.md §14).

Turns the ad-hoc perf/ census scripts into a first-class autotuner:

  - ``roofline``  — per-generation hardware tables + a scorer that converts
    a compiled program's cost/memory analysis into a predicted lower-bound
    ms/step, a binding-resource verdict, and a fits/OOM check.
  - ``search``    — candidate enumeration (flash-attention block grid pruned
    against the Mosaic VMEM double-buffer budget, ``TPUFRAME_XLA_OPTS``
    compiler-option sets, batch shapes) + the AOT sweep driver that compiles
    each candidate on a compile-only topology.
  - ``db``        — the persistent tuning database consulted by ``train.py``,
    ``bench.py`` and ``ops/flash_attention.py`` at startup.  Precedence:
    env override > measured > predicted > default.

``python -m tpuframe.tune sweep --topology v5e:2x2`` runs the whole thing
CPU-only — no TPU attached.

This package root is import-light on purpose: ``db``/``roofline`` are pure
stdlib so the flash-attention import-time lookup and the analysis-gate
self-check stay cheap; only ``search`` touches jax, and lazily.
"""

import os

from tpuframe.tune import db as db  # noqa: F401
from tpuframe.tune import roofline as roofline  # noqa: F401


def check(db_path: str | None = None) -> list:
    """The CI self-check (registered in the ``python -m tpuframe.analysis``
    gate and exposed as ``python -m tpuframe.tune check``): hardware-table
    sanity (the v5e roofline anchors must keep reproducing PERF.md §2),
    tuning-DB schema validation, and a TF106 self-lint of the tuner's own
    flag plumbing — the subsystem that hands out compiler options must not
    itself mutate XLA_FLAGS after backend init.  Returns problem strings;
    empty means healthy."""
    problems = list(roofline.check_tables())

    path = db_path or db.default_db_path()
    if os.path.exists(path):
        try:
            import json

            with open(path) as f:
                data = json.load(f)
            problems += [f"{os.path.basename(path)}: {p}"
                         for p in db.validate(data)]
        except Exception as e:  # noqa: BLE001
            problems.append(f"{path}: unreadable ({e})")

    from tpuframe.analysis import source_lint

    here = os.path.dirname(os.path.abspath(__file__))
    pkg = os.path.dirname(here)
    targets = [os.path.join(here, f) for f in sorted(os.listdir(here))
               if f.endswith(".py")]
    targets += [os.path.join(pkg, "utils", "xla_opts.py"),
                os.path.join(pkg, "utils", "compile_cache.py")]
    for target in targets:
        if not os.path.exists(target):
            problems.append(f"self-lint target missing: {target}")
            continue
        with open(target) as f:
            src = f.read()
        for finding in source_lint.lint_source(src, path=target):
            if finding.rule == "TF106":
                problems.append(f"self-lint {os.path.basename(target)}:"
                                f"{finding.line} {finding.message}")
    return problems
