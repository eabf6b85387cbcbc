"""Perf experiment: where does the ResNet-50 step time go?

Decomposes the step cost without a device trace, by compiling and timing
nested sub-programs:

  fwd            : inference forward (train=False)
  fwd_train      : forward with batch-stat mutation
  grad           : value_and_grad (fwd+bwd), no optimizer
  full           : the real train step (grad + pmean-less update)

and prints XLA cost analysis (flops / bytes accessed) for each, which gives
an analytic roofline: t_mxu = flops / 197e12, t_hbm = bytes / 8.1e11 (v5e).
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


def log(m):
    print(f"[exp] {m}", file=sys.stderr, flush=True)


def time_fn(make_chain, *args):
    """Per-iteration time of a data-dependent chain (perf/_common.py).

    chain=16: the difference t_16 - t_1 must clear host jitter even for
    the ~20ms fwd program."""
    from _common import timeit_chain

    return timeit_chain(make_chain, *args, chain=16, log=log)


def cost(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return ca.get("flops", 0), ca.get("bytes accessed", 0)
    except Exception:
        return 0, 0


def main():
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0.5, 0.25, size=(BATCH, 224, 224, 3)),
                    jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, size=(BATCH,)), jnp.int32)
    variables = model.init(jax.random.key(0), x[:2])
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)
    params, bstats = variables["params"], variables["batch_stats"]

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    state = step_lib.TrainState.create(
        params, tx, model_state={"batch_stats": bstats})
    train_step = step_lib.make_train_step(loss_fn, tx, None, donate=False)
    batch = {"image": x, "label": y}

    # Sub-program timings are DATA-DEPENDENT chains (lax.scan feeding a
    # 1e-30-scaled summary of iteration i's output into iteration i+1's
    # input), so dispatch overhead cancels in the difference.  1e-30 keeps
    # the carry numerically unchanged in bf16 while remaining opaque to
    # XLA's simplifier.  Per-iteration time comes from
    # timeit_chain's (t_N - t_1)/(N-1) difference.

    # -- fwd (inference) --
    def fwd_chain(n):
        def g(im, p, s):
            def body(xc, _):
                logits = model.apply({"params": p, **s}, xc, train=False)
                dep = (1e-30 * jnp.sum(logits)).astype(xc.dtype)
                return xc + dep, None
            xc, _ = jax.lax.scan(body, im, None, length=n)
            return xc
        return jax.jit(g)

    log("timing fwd(infer)...")
    t = time_fn(fwd_chain, x, params, {"batch_stats": bstats})
    fwd = jax.jit(lambda p, s, im: model.apply(
        {"params": p, **s}, im, train=False))
    log("cost-analysis fwd(infer)...")
    c = cost(fwd.lower(params, {"batch_stats": bstats}, x).compile())
    log(f"fwd(infer)  : {t*1e3:7.1f} ms  flops={c[0]:.3e} bytes={c[1]:.3e}")

    # -- fwd train (batch stats) --
    def fwd_t_chain(n):
        def g(im, p, s):
            def body(carry, _):
                xc, stats = carry
                logits, mutated = model.apply(
                    {"params": p, **stats}, xc, train=True,
                    mutable=["batch_stats"])
                dep = (1e-30 * jnp.sum(logits)).astype(xc.dtype)
                return (xc + dep, dict(mutated)), None
            (xc, _), _ = jax.lax.scan(body, (im, s), None, length=n)
            return xc
        return jax.jit(g)

    log("timing fwd(train)...")
    t = time_fn(fwd_t_chain, x, params, {"batch_stats": bstats})
    fwd_t = jax.jit(lambda p, s, im: model.apply(
        {"params": p, **s}, im, train=True, mutable=["batch_stats"]))
    log("cost-analysis fwd(train)...")
    c = cost(fwd_t.lower(params, {"batch_stats": bstats}, x).compile())
    log(f"fwd(train)  : {t*1e3:7.1f} ms  flops={c[0]:.3e} bytes={c[1]:.3e}")

    # -- grad --
    r = jax.random.key(1)

    def grad_chain(n):
        def g(im, p, s):
            def body(carry, _):
                xc, stats = carry
                (loss, (stats, _)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(
                        p, stats, {"image": xc, "label": y}, r)
                gsum = sum(jnp.sum(g.astype(jnp.float32))
                           for g in jax.tree.leaves(grads))
                dep = (1e-30 * (loss + gsum)).astype(xc.dtype)
                return (xc + dep, stats), None
            (xc, _), _ = jax.lax.scan(body, (im, s), None, length=n)
            return xc
        return jax.jit(g)

    log("timing grad...")
    t = time_fn(grad_chain, x, params, {"batch_stats": bstats})

    def just_grad(p, s, b, r):
        return jax.value_and_grad(loss_fn, has_aux=True)(p, s, b, r)
    gr = jax.jit(just_grad)
    log("cost-analysis grad...")
    c = cost(gr.lower(params, {"batch_stats": bstats}, batch, r).compile())
    log(f"grad(f+b)   : {t*1e3:7.1f} ms  flops={c[0]:.3e} bytes={c[1]:.3e}")

    # -- full step --
    log("timing full step...")
    new, m = train_step(state, batch)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    # Seed with the warmup's OUTPUT (`state` was donated to it).
    cur = new
    for _ in range(STEPS):
        cur, m = train_step(cur, batch)
    jax.block_until_ready(m)
    t = (time.perf_counter() - t0) / STEPS
    c = cost(train_step.lower(state, batch).compile())
    log(f"full step   : {t*1e3:7.1f} ms  flops={c[0]:.3e} bytes={c[1]:.3e}")
    log(f"roofline: t_mxu(full)={c[0]/197e12*1e3:.1f} ms  "
        f"t_hbm(full)={c[1]/8.1e11*1e3:.1f} ms")
    log(f"imgs/s at full: {BATCH/t:.1f}")


if __name__ == "__main__":
    main()
