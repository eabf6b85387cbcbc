"""The training step the plain references follow, over any loss: clip by
global norm, then AdamW on the job's schedule, three steps' worth of
buffers at most.  Plain ``jax.numpy``; imports nothing of the program.
(``trinity_mini.py`` and ``lm124m.py`` carry the same step inline, from
before this module: a ``benchmark`` PR's to point them here.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from _common import decays

B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def train_steps(loss_fn, learning_rate, job: dict, params: dict,
                batches: list, *, keep_rows: int | None = None) -> dict:
    """Follow the job's first ``len(batches)`` steps from ``params`` with
    ``loss_fn(params, batch)`` and ``learning_rate(job, step)``.

    Returns ``{"losses": [...], "opt_grad": tree, "delta": tree}``: each
    step's loss, the first gradient as the optimizer gets it (after the
    clip), and the parameters' change after all the steps.  ``keep_rows``
    is the planted fault "part of the batch left out": the rows past it,
    or, of a one-row batch, the second half of the sequence."""
    wd, clip = float(job.get("weight_decay", 0.0)), job.get("grad_clip_norm")

    def step(params, mu, nu, batch, i):
        if keep_rows is not None:
            if batch["input_ids"].shape[0] > 1:
                batch = {k: v[:keep_rows] for k, v in batch.items()}
            else:
                half = batch["input_ids"].shape[1] // 2
                batch = dict(batch, labels=batch["labels"].at[:, half:]
                             .set(-100))
        val, grads = jax.value_and_grad(loss_fn)(params, batch)
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(grads)))
            grads = jax.tree.map(
                lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
        mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
        t = i + 1.0
        lr = learning_rate(job, i)

        def upd(path, p, m, v):
            u = (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t)) + ADAM_EPS)
            if decays(path):
                u = u + wd * p
            return p - lr * u

        params = jax.tree_util.tree_map_with_path(upd, params, mu, nu)
        return params, mu, nu, val, grads

    # one compiled step that writes over its own buffers: the caller's
    # parameters are copied once and left be (with the first gradient kept,
    # six copies of the weights at most)
    step = jax.jit(step, donate_argnums=(0, 1, 2))
    start = params
    params = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, val, grads = step(params, mu, nu, batch,
                                          jnp.asarray(i, jnp.float32))
        losses.append(float(val))
        if i == 0:
            first = grads
        del grads
    delta = jax.tree.map(lambda a, b: a - b, params, start)
    return {"losses": losses, "opt_grad": first, "delta": delta}
