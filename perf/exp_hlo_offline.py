"""OFFLINE byte census: AOT-compile the exact ResNet-50 train step against
a v5e topology (compile-only libtpu, no chip) and attribute the HBM
traffic from the optimized HLO.

Discovery (2026-07-31): the sandbox bundles `libtpu.so`, and
`jax.experimental.topologies.get_topology_desc("v5e:2x2", platform="tpu")`
yields compile-only TpuDevices — `jit(...).lower(...).compile()` then
produces the REAL TPU executable artifacts (optimized HLO with layouts,
`cost_analysis`, `memory_analysis`) on the CPU host.  The census needs
no chip; `exp_hlo_dump.py` (the on-chip twin) remains only as a
cross-check that the on-chip compiler makes the same choices.

Run from the repo root on the CPU backend:
    JAX_PLATFORMS=cpu python perf/exp_hlo_offline.py

Outputs perf/results/resnet_step_hlo_offline.txt + a JSON summary line.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import (ensure_cpu_backend, hold_aot_lock,  # noqa: E402
                     to_shape_structs)

ensure_cpu_backend()
hold_aot_lock()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

BATCH = int(os.environ.get("B", "512"))
BN = os.environ.get("BN", "flax")   # flax | folded | fused (PERF.md §7 A/B)
REMAT = os.environ.get("REMAT", "0") == "1"
STEM = os.environ.get("STEM", "conv")
# Compile-only topology target.  "v5e:2x2" = the bench chip's family;
# "v4:2x2x2" = the north-star v4 family (32 GB HBM/chip, 275 TFLOPs
# bf16 peak — several v5e capacity verdicts flip there, VERDICT r4 #5).
TOPO = os.environ.get("TOPO", "v5e:2x2")


from _common import hlo_shape_census, hlo_nbytes  # noqa: E402


def log(m):
    print(f"[hlo-offline] {m}", file=sys.stderr, flush=True)


def main():
    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import step as step_lib

    log(f"building {TOPO} compile-only topology...")
    topo = topologies.get_topology_desc(TOPO, platform="tpu")
    dev = topo.devices[0]
    mesh = Mesh(np.array([dev]), ("data",))
    repl = NamedSharding(mesh, P())

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16, bn=BN,
                            remat=REMAT, stem=STEM)
    log(f"model variant: bn={BN} remat={REMAT} stem={STEM}")
    # Abstract init on the CPU backend gives the param STRUCTURE; the AOT
    # compile only needs ShapeDtypeStructs.
    log("abstract-init model...")
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
        jax.random.key(0))
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx, model_state={"batch_stats": v["batch_stats"]}),
        variables)
    train_step = step_lib.make_train_step(loss_fn, tx, None, donate=False)

    batch = {"image": jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.bfloat16,
                                           sharding=repl),
             "label": jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=repl)}
    state = to_shape_structs(state, repl)

    log(f"AOT lower+compile (B={BATCH}) against {dev!r}...")
    compiled = jax.jit(train_step._fun if hasattr(train_step, "_fun")
                       else train_step).lower(state, batch).compile()

    ca = compiled.cost_analysis() or {}
    flops = ca.get("flops", 0.0)
    byts = ca.get("bytes accessed", 0.0)
    log(f"cost_analysis: flops={flops:.4g} bytes={byts:.4g} "
        f"({byts/1e9:.1f} GB/step, {byts/BATCH/1e6:.1f} MB/img)")
    try:
        ma = compiled.memory_analysis()
        log(f"memory: argument={ma.argument_size_in_bytes/1e9:.2f}GB "
            f"output={ma.output_size_in_bytes/1e9:.2f}GB "
            f"temp={ma.temp_size_in_bytes/1e9:.2f}GB")
    except Exception as e:  # noqa: BLE001
        log(f"memory_analysis unavailable: {e}")

    txt = compiled.as_text()
    suffix = "" if (BN, REMAT, STEM) == ("flax", False, "conv") else (
        f"_{BN}" + ("_remat" if REMAT else "") +
        ("_s2d" if STEM != "conv" else ""))
    from _common import topo_tag_suffix

    suffix += topo_tag_suffix(TOPO, "v5e:2x2")
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "results", f"resnet_step_hlo_offline{suffix}.txt")
    with open(out_path, "w") as f:
        f.write(txt)
    log(f"wrote {out_path} ({len(txt)/1e6:.1f} MB)")

    log("top shapes by total padded bytes (count x padded-est):")
    for k, n in hlo_shape_census(txt)[:25]:
        log(f"  {n:5d} x {k}  ~{hlo_nbytes(k)/1e6:.1f} MB each")

    print(json.dumps({"batch": BATCH, "bn": BN, "remat": REMAT, "stem": STEM,
                      "flops": flops, "bytes": byts,
                      "gb_per_step": round(byts / 1e9, 2),
                      "mb_per_image": round(byts / BATCH / 1e6, 2),
                      "hlo_chars": len(txt),
                      "source": f"offline AOT {TOPO} topology compile"}))


if __name__ == "__main__":
    main()
