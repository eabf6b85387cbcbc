"""Scaling-efficiency projection 8 -> 32 chips — the declared methodology.

The driver metric (BASELINE.json:2) is 8->32-chip scaling efficiency, but
this sandbox exposes ONE chip (BASELINE.md). SURVEY.md §6/§7 ("hard part
5") asks for an honest methodology defined up front; this script is it:

1. MEASURED: compile the real DP train step on a virtual 8-device mesh and
   read the cross-replica traffic out of the compiled HLO — the all-reduce
   operand bytes per step (for ResNet-50 DP: the fp32 gradient tree, ~97 MB,
   fused into one variadic all-reduce; asserted by tests/test_fusion.py).
   Collective bytes are a property of the program, not of the device, so
   the CPU-mesh HLO is the TPU program's traffic model.
2. MEASURED: single-chip step time from bench.py on the real chip.
3. DOCUMENTED CONSTANTS: per-chip ICI bandwidth from public spec sheets.
4. MODEL: bidirectional-ring all-reduce cost 2*(N-1)/N * B / BW per step,
   reported both unoverlapped (worst case: efficiency = t_c / (t_c + t_ar))
   and fully-overlapped (best case: t = max(t_c, t_ar)) — the truth lands
   between; XLA's latency-hiding scheduler targets the overlapped end.

Run on CPU (the HLO half) — it prints the projection table and the exact
formula inputs so a reader can re-derive every number.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# --bytes-only N: print {"n_devices": N, "ar_bytes": B} as JSON and exit —
# the mode tests/test_scaling32.py uses to verify the projection's central
# assumption (all-reduce bytes are N-independent) at BOTH mesh endpoints.
_N_DEVICES = 8
if "--bytes-only" in sys.argv:
    _N_DEVICES = int(sys.argv[sys.argv.index("--bytes-only") + 1])

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_N_DEVICES}")

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax

jax.config.update("jax_platforms", "cpu")

from tpuframe import models
from tpuframe.models import losses
from tpuframe.parallel import mesh as mesh_lib
from tpuframe.parallel import step as step_lib

# Public spec-sheet constants (bytes/s). v5e: 1600 Gbps ICI per chip
# (Google Cloud TPU v5e spec); v4: 2400 Gbps. Ring all-reduce uses the
# bidirectional torus links; we model per-chip injection bandwidth.
ICI_BYTES_PER_S = {"v4": 300e9, "v5e": 200e9}

# Measured on the bench chip (BASELINE.md round 3): batch 256/chip.
MEASURED_IMG_PER_S = 2385.0
MEASURED_BATCH = 256
CHIP = "v5e"


def collective_bytes_per_step(n_devices: int = 8) -> int:
    """Compile the DP ResNet-50 step on an ``n_devices`` virtual mesh; sum
    the all-reduce operand bytes in the optimized HLO."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n_devices))
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    batch = max(16, 2 * n_devices)
    x = jnp.asarray(rng.normal(size=(batch, 64, 64, 3)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, size=(batch,)), jnp.int32)
    variables = model.init(jax.random.key(0), x[:2])
    tx = optax.sgd(0.1, momentum=0.9)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        return losses.softmax_cross_entropy(logits, batch["label"]), (
            dict(mutated), {})

    state = step_lib.TrainState.create(
        variables["params"], tx,
        model_state={"batch_stats": variables["batch_stats"]})
    state = step_lib.replicate_state(state, mesh)
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False)
    batch = {"image": jax.device_put(x, mesh_lib.batch_sharding(mesh)),
             "label": jax.device_put(y, mesh_lib.batch_sharding(mesh))}
    txt = step.lower(state, batch).compile().as_text()

    total = 0
    # HLO form: %all-reduce.N = (f32[256]{0}, ...) all-reduce(%op, ...) —
    # the reduced tensors are the RESULT tuple's types; operands are
    # unshaped value refs.  Sum result bytes across every all-reduce.
    for line in txt.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(", line)
        if not m:
            continue
        for dt, dims in re.findall(r"(f32|bf16|f16|s32)\[([0-9,]*)\]",
                                   m.group(1)):
            size = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4}[dt]
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * size
    return total


def project(ar_bytes: int):
    t_c = MEASURED_BATCH / MEASURED_IMG_PER_S  # compute-side step seconds
    bw = ICI_BYTES_PER_S[CHIP]
    print(f"inputs: all-reduce bytes/step={ar_bytes/1e6:.1f}MB "
          f"(compiled HLO, 8-dev mesh), single-chip step={t_c*1e3:.1f}ms "
          f"({MEASURED_IMG_PER_S} img/s at batch {MEASURED_BATCH}, "
          f"BASELINE.md), ICI={bw/1e9:.0f}GB/s/chip ({CHIP} spec)")
    print(f"{'chips':>6} {'t_ar(ms)':>9} {'eff(no-overlap)':>16} "
          f"{'eff(overlapped)':>16}")
    rows = {}
    for n in (8, 16, 32, 64):
        t_ar = 2 * (n - 1) / n * ar_bytes / bw
        eff_worst = t_c / (t_c + t_ar)
        eff_best = t_c / max(t_c, t_ar)
        rows[n] = (t_ar, eff_worst, eff_best)
        print(f"{n:>6} {t_ar*1e3:>9.2f} {eff_worst:>15.1%} "
              f"{eff_best:>15.1%}")
    w8, b8 = rows[8][1], rows[8][2]
    w32, b32 = rows[32][1], rows[32][2]
    print(f"8->32 relative efficiency: worst {w32/w8:.1%}, "
          f"best {b32/b8:.1%} (target: >=90% of the Horovod-GPU baseline, "
          f"BASELINE.json:5; the Horovod paper's own anchor is ~88% at "
          f"128 GPUs)")


if __name__ == "__main__":
    b = collective_bytes_per_step(_N_DEVICES)
    if "--bytes-only" in sys.argv:
        import json

        print(json.dumps({"n_devices": _N_DEVICES, "ar_bytes": b}))
    else:
        project(b)
