"""Perf experiment: per-step scalar-fetch sync vs async chained dispatch.

A loop that fetches a scalar every step serializes host and device.
Fetching only the FINAL step's loss is also a full barrier for the whole
chain (each step consumes the previous state), while letting the host run
ahead and the device pipeline dispatch.  This measures both.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpuframe import models
from tpuframe.models import losses
from tpuframe.parallel import step as step_lib
from tpuframe.utils import compile_cache

compile_cache.enable()

BATCH = int(os.environ.get("B", "512"))
STEPS = int(os.environ.get("N", "8"))
TRACE = os.environ.get("TRACE", "")


def log(m):
    print(f"[exp] {m}", file=sys.stderr, flush=True)


def main():
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = rng.normal(0.5, 0.25, size=(BATCH, 224, 224, 3)).astype(jnp.bfloat16)
    y = rng.integers(0, 1000, size=(BATCH,)).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(x[:2]))
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    state = step_lib.TrainState.create(
        variables["params"], tx,
        model_state={"batch_stats": variables["batch_stats"]})
    train_step = step_lib.make_train_step(loss_fn, tx, None, donate=True)
    batch = {"image": jax.device_put(x), "label": jax.device_put(y)}

    log(f"compile+warmup batch={BATCH}")
    t0 = time.perf_counter()
    for i in range(3):
        state, metrics = train_step(state, batch)
        float(metrics["loss"])
    log(f"warmup done in {time.perf_counter()-t0:.1f}s")

    # Mode A: per-step scalar fetch (round-2 bench behavior)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, metrics = train_step(state, batch)
        float(metrics["loss"])
    dt_a = time.perf_counter() - t0
    log(f"A per-step sync : {STEPS*BATCH/dt_a:8.1f} img/s  ({dt_a/STEPS*1e3:.1f} ms/step)")

    # Mode B: async chain, single final fetch
    t0 = time.perf_counter()
    last = None
    for _ in range(STEPS):
        state, metrics = train_step(state, batch)
        last = metrics["loss"]
    float(last)
    dt_b = time.perf_counter() - t0
    log(f"B chained async : {STEPS*BATCH/dt_b:8.1f} img/s  ({dt_b/STEPS*1e3:.1f} ms/step)")

    # Mode C: block_until_ready on the final state (check the early-return claim)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(state)
    dt_c = time.perf_counter() - t0
    log(f"C block_until_ready: {STEPS*BATCH/dt_c:8.1f} img/s  ({dt_c/STEPS*1e3:.1f} ms/step)")
    # sanity: fetch loss after, should be ~instant if C really waited
    t0 = time.perf_counter()
    float(metrics["loss"])
    log(f"C residual fetch after block: {time.perf_counter()-t0:.3f}s")

    if TRACE:
        log(f"tracing {STEPS} steps to {TRACE}")
        with jax.profiler.trace(TRACE):
            for _ in range(STEPS):
                state, metrics = train_step(state, batch)
            float(metrics["loss"])
        log("trace done")


if __name__ == "__main__":
    main()
