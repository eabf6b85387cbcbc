"""The 8->32 scaling projection's measured input, verified at BOTH mesh
endpoints (round-3 verdict missing #6 / SURVEY.md §6, §7 hard part 5).

perf/scaling_projection.py models ring all-reduce cost as
``2*(N-1)/N * B / BW`` with B taken from the compiled 8-device HLO.  The
load-bearing assumption is that B — the per-step cross-replica payload —
does not grow with N (only the ring factor does).  Nothing before this
test verified the compiled 32-device program actually ships those bytes.

Each endpoint compiles in its own subprocess because the forced host
device count is fixed at backend init (the test session is pinned to 8).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "perf", "scaling_projection.py")


def _bytes_at(n_devices: int) -> int:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    out = subprocess.run(
        [sys.executable, SCRIPT, "--bytes-only", str(n_devices)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == n_devices
    return rec["ar_bytes"]


@pytest.mark.slow
def test_allreduce_bytes_match_projection_model_at_8_and_32():
    b8 = _bytes_at(8)
    b32 = _bytes_at(32)

    # The projection's B: the fp32 gradient tree of ResNet-50 (~25.5M
    # params -> ~102 MB) plus nothing else.  Check against the analytic
    # param count rather than a magic constant.
    from tpuframe import models
    import jax
    import jax.numpy as jnp

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((2, 64, 64, 3),
                                                        jnp.bfloat16)))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(variables["params"]))
    grad_bytes = 4 * n_params

    # B is N-independent: the 32-way program ships the same payload the
    # 8-way HLO measured (the ring factor 2*(N-1)/N is cost model, not
    # payload).  Allow 2% slack for N-dependent scalar reductions (loss,
    # batch-stats counters).
    assert abs(b32 - b8) <= 0.02 * b8, (b8, b32)
    # And B is what the projection says it is: the fp32 grad tree (batch
    # stats ride the same fused all-reduce, hence the upper margin).
    assert 0.95 * grad_bytes <= b8 <= 1.15 * grad_bytes, (
        b8, grad_bytes, n_params)
