"""``TPUFRAME_XLA_OPTS`` parsing, shared by bench.py, train.py and the
tune sweep.

Format: ``key=value,key=value`` (e.g.
``xla_tpu_enable_latency_hiding_scheduler=true``).  The resulting dict is
passed as ``jax.jit(..., compiler_options=...)`` — the options travel
inside the compile request, so they apply to one program and not the
process, and they need no env mutation at all (TF106).
"""

from __future__ import annotations

import os

ENV_VAR = "TPUFRAME_XLA_OPTS"


def parse(spec: str) -> dict:
    """'k=v,k=v' -> dict.  Raises ValueError listing every bad entry."""
    pairs = [kv.strip() for kv in spec.split(",") if kv.strip()]
    bad = [kv for kv in pairs
           if "=" not in kv or not kv.split("=", 1)[0].strip()
           or not kv.split("=", 1)[1].strip()]
    if bad:
        raise ValueError(f"{ENV_VAR} entries need key=value, got {bad!r}")
    return {k.strip(): v.strip() for k, v in
            (kv.split("=", 1) for kv in pairs)}


def from_env(var: str = ENV_VAR) -> dict | None:
    """The env var parsed, or None when unset/empty (so callers can fall
    through to the tuning DB: env override > measured > predicted >
    default)."""
    spec = os.environ.get(var, "")
    return parse(spec) if spec.strip() else None


def format_opts(opts: dict) -> str:
    """Inverse of :func:`parse` — the env-var spelling of an option set
    (used by tune records' env_overrides)."""
    return ",".join(f"{k}={v}" for k, v in sorted(opts.items()))
