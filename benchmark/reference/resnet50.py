"""Plain reference of the ``resnet50`` configuration: ResNet-50 v1.5
(He et al. 2015; torchvision's ``resnet50``: stride 2 on the 3x3 of a
bottleneck) with its training step, in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision.  Imports nothing of the program.

Departures from torchvision, both the program's own and followed here so
that the two can be compared: NHWC layout, and ``SAME`` padding (at 224
the 7x7/2 stem pads (2, 3), torchvision pads (3, 3)).  Batch statistics
are the biased ones of the batch (training mode); running statistics are
not compared and not kept.

The step is SGD with Nesterov momentum, weight decay coupled into the
gradient for every leaf not named ``bias`` or ``scale``, label smoothing,
and a cosine schedule, as the job's parameters state.

``quant="int8"`` computes every convolution and the classifier, forward
and backward, on operands rounded to 8-bit integers with one scale per
tensor: the control that has to come out as not correct.  ``keep_rows`` computes on the first rows of each batch only
and takes the mean over them: the planted fault "half of the batch left
out".

Each bottleneck is recomputed in the backward pass (``jax.checkpoint``),
so that float32 at the cell's own batch fits one chip.
"""

from __future__ import annotations

import math

import os
import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import decays, learning_rate, nest, product  # noqa: E402

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5


def _names(cfg: dict) -> list[tuple[str, tuple]]:
    """``(path, shape)`` of every parameter, in the program's naming."""
    w = cfg["width"]
    out = [("stem_conv/kernel", (7, 7, 3, w)), ("stem_bn/scale", (w,)),
           ("stem_bn/bias", (w,))]
    cin, idx = w, 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            p = f"Bottleneck_{idx}"
            for k, (kk, ci, co) in enumerate(
                    [(1, cin, f), (3, f, f), (1, f, 4 * f)]):
                out += [(f"{p}/Conv_{k}/kernel", (kk, kk, ci, co)),
                        (f"{p}/BatchNorm_{k}/scale", (co,)),
                        (f"{p}/BatchNorm_{k}/bias", (co,))]
            if cin != 4 * f or stride != 1:
                out += [(f"{p}/downsample_conv/kernel", (1, 1, cin, 4 * f)),
                        (f"{p}/downsample_bn/scale", (4 * f,)),
                        (f"{p}/downsample_bn/bias", (4 * f,))]
            cin, idx = 4 * f, idx + 1
    out += [("Dense_0/kernel", (cin, cfg["num_classes"])),
            ("Dense_0/bias", (cfg["num_classes"],))]
    return out




def init_weights(cfg: dict, seed: int) -> dict:
    """``{"params": ..., "model_state": {"batch_stats": ...}}`` from the
    seed, float32, made on the device in one jitted call.  He-normal
    kernels (fan-out), scales near 1 (near 0.25 on the last BN of a
    bottleneck) and biases near 0, with a little noise so that no leaf's
    gradient is degenerate."""
    names = _names(cfg)

    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(names):
            k = jax.random.fold_in(key, i)
            leaf = path.rsplit("/", 1)[-1]
            if leaf == "kernel" and len(shape) == 4:
                fan_out = shape[0] * shape[1] * shape[3]
                val = jax.random.normal(k, shape) * math.sqrt(2.0 / fan_out)
            elif leaf == "kernel":
                val = jax.random.normal(k, shape) * 0.01
            elif leaf == "scale":
                # the last BN of a bottleneck starts small (torchvision's
                # zero_init_residual, but not zero: a zero scale would
                # leave the whole branch without a gradient)
                base = 0.25 if path.endswith("BatchNorm_2/scale") else 1.0
                val = base * (1.0 + 0.1 * jax.random.normal(k, shape))
            else:
                val = 0.1 * jax.random.normal(k, shape)
            flat[path] = val.astype(jnp.float32)
        stats = {}
        for path, shape in names:
            if path.endswith("/scale"):
                base = path[: -len("/scale")]
                stats[base + "/mean"] = jnp.zeros(shape, jnp.float32)
                stats[base + "/var"] = jnp.ones(shape, jnp.float32)
        return {"params": nest(flat),
                "model_state": {"batch_stats": nest(stats)}}

    return jax.jit(make)(jax.random.key(seed % (2 ** 31 - 1)))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _conv(x, w, stride, quant):
    return product(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST),
        quant)(x, w)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride, q):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], 1, q),
                        p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride, q),
                        p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"]["kernel"], 1, q), p["BatchNorm_2"])
    if "downsample_conv" in p:
        x = _bn(_conv(x, p["downsample_conv"]["kernel"], stride, q),
                p["downsample_bn"])
    return jax.nn.relu(x + y)


def forward(cfg: dict, params: dict, images, *, quant: str | None = None):
    """Training-mode logits ``[B, classes]`` in float32."""
    q = quant
    x = images.astype(jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, params["stem_conv"]["kernel"], 2, q),
                        params["stem_bn"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    idx = 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            block = jax.checkpoint(
                lambda p, x, s=stride: _bottleneck(p, x, s, q))
            x = block(params[f"Bottleneck_{idx}"], x)
            idx += 1
    x = jnp.mean(x, axis=(1, 2))
    d = params["Dense_0"]
    dense = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST), quant)
    return dense(x, d["kernel"]) + d["bias"]


def loss(cfg: dict, job: dict, params: dict, batch: dict,
         *, quant: str | None = None):
    logits = forward(cfg, params, batch["image"], quant=quant)
    n = logits.shape[-1]
    ls = float(job.get("label_smoothing", 0.0))
    off = ls / (n - 1) if ls > 0 else 0.0
    soft = jax.nn.one_hot(batch["label"], n) * (1.0 - ls - off) + off
    return -jnp.mean(jnp.sum(soft * jax.nn.log_softmax(logits), axis=-1))


# --------------------------------------------------------------------------
# the training step
# --------------------------------------------------------------------------

def train_steps(cfg: dict, job: dict, params: dict, batches: list,
                *, quant: str | None = None, keep_rows: int | None = None):
    """Follow the job's first ``len(batches)`` steps from ``params``.

    Returns ``{"losses": [...], "opt_grad": tree, "delta": tree}``: each
    step's loss, the first gradient as the optimizer gets it (after the
    coupled weight decay: SGD's momentum buffer after one step), and the
    parameters' change after all the steps."""
    mom, wd = float(job["momentum"]), float(job.get("weight_decay", 0.0))

    @jax.jit
    def step(params, trace, batch, i):
        if keep_rows is not None:
            batch = {k: v[:keep_rows] for k, v in batch.items()}
        val, grads = jax.value_and_grad(
            lambda p: loss(cfg, job, p, batch, quant=quant))(params)
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g, p: g + wd * p if decays(path) else g,
            grads, params)
        trace = jax.tree.map(lambda g, t: g + mom * t, grads, trace)
        lr = learning_rate(job, i)
        params = jax.tree.map(lambda p, g, t: p - lr * (g + mom * t),
                              params, grads, trace)
        return params, trace, val, grads

    start = params
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, trace, val, grads = step(params, trace, batch,
                                         jnp.asarray(i, jnp.float32))
        losses.append(float(val))
        if i == 0:
            first = grads
    delta = jax.tree.map(lambda a, b: a - b, params, start)
    return {"losses": losses, "opt_grad": first, "delta": delta}
