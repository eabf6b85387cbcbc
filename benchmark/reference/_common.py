"""What the plain references share: the tree of named parameters, the
int8 control's arithmetic, and the job's schedule and decay mask.  Plain
``jax.numpy``; imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` to ``{"a": {"b": {"c": x}}}``."""
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = val
    return tree


def int8(x):
    """Round to 8-bit integers with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def product(fn, quant: str | None):
    """``fn(a, b)``, a product of two operands, as ``quant`` computes it:
    with ``"int8"`` both operands are rounded forward, and backward the
    incoming gradient and the kept operands are rounded before the two
    transposed products, as a training path in int8 would."""
    if quant is None:
        return fn
    if quant != "int8":
        raise ValueError(f"unknown quant {quant!r}")

    @jax.custom_vjp
    def low(a, b):
        return fn(int8(a), int8(b))

    def fwd(a, b):
        return low(a, b), (a, b)

    def bwd(kept, g):
        _, vjp = jax.vjp(fn, int8(kept[0]), int8(kept[1]))
        return vjp(int8(g))

    low.defvjp(fwd, bwd)
    return low


def decays(path) -> bool:
    """Weight decay reaches every leaf not named as a bias or a scale."""
    return str(path[-1].key) not in ("bias", "scale", "b")


def learning_rate(job: dict, step):
    """The job's schedule at ``step`` (no warm-up): the peak, scaled by
    batch/256 where the job says so, constant or on a cosine to zero over
    ``total_steps``."""
    peak = job["base_lr"] * (job["global_batch"] / 256.0
                             if job.get("scale_lr_by_batch", True) else 1.0)
    if job.get("schedule", "cosine") == "constant":
        return peak
    frac = jnp.minimum(step / max(job["total_steps"], 1), 1.0)
    return peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
