"""Plain reference of the ``lm124m`` configuration: a pre-LN decoder-only
transformer at GPT-2 small's widths (hidden 768, 12 layers, 12 heads of
64, MLP 3072) in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision: forward, loss, gradients and the AdamW step.  Imports
nothing of the program.

What differs from GPT-2 small and is the program's decoder (the
configuration file lists it under ``differs_from_source``): rotary
positions (theta 10000, adjacent pairs) in place of learned ones, no
biases, LayerNorm with a scale only (epsilon 1e-6), an untied output head.
The vocabulary (50257) and the tanh-approximated GELU are GPT-2's.

``quant="int8"`` computes every matrix product, forward and backward, on
operands rounded to 8-bit integers with one scale per tensor: the control
that has to come out as not correct.  ``keep_rows``
is the planted fault "half of the batch left out".

Each block is recomputed in the backward pass (``jax.checkpoint``), so
that float32 at the cell's own batch fits one chip.
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
LN_EPS = 1e-6


def _names(cfg: dict) -> list[tuple[str, tuple]]:
    h, n, ff, v = (cfg["hidden_size"], cfg["num_heads"],
                   cfg["intermediate_size"], cfg["vocab_size"])
    d = h // n
    out = [("embed/embedding", (v, h))]
    for i in range(cfg["num_layers"]):
        b = f"block_{i}"
        out += [(f"{b}/attn_ln/scale", (h,)),
                (f"{b}/attn/query/kernel", (h, n, d)),
                (f"{b}/attn/key/kernel", (h, n, d)),
                (f"{b}/attn/value/kernel", (h, n, d)),
                (f"{b}/attn/out/kernel", (n, d, h)),
                (f"{b}/mlp_ln/scale", (h,)),
                (f"{b}/up/kernel", (h, ff)),
                (f"{b}/down/kernel", (ff, h))]
    out += [("final_ln/scale", (h,)), ("lm_head/kernel", (h, v))]
    return out




def init_weights(cfg: dict, seed: int) -> dict:
    """``{"params": ..., "model_state": {}}`` from the seed, float32, made
    on the device in one jitted call: kernels normal with variance
    1/fan_in, the embedding normal 0.02 as GPT-2's, scales near 1."""
    names = _names(cfg)

    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(names):
            k = jax.random.fold_in(key, i)
            leaf = path.rsplit("/", 1)[-1]
            if leaf == "scale":
                val = 1.0 + 0.1 * jax.random.normal(k, shape)
            elif leaf == "embedding":
                val = 0.02 * jax.random.normal(k, shape)
            else:
                fan_in = shape[0] * shape[1] if path.endswith(
                    "out/kernel") else shape[0]
                val = jax.random.normal(k, shape) / math.sqrt(fan_in)
            flat[path] = val.astype(jnp.float32)
        return {"params": nest(flat), "model_state": {}}

    return jax.jit(make)(jax.random.key(seed % (2 ** 31 - 1)))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _ln(x, scale):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * scale


def _rope(x, theta: float):
    """x ``[B, S, N, D]``; adjacent pairs ``(2i, 2i+1)`` rotate together."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _block(cfg: dict, p: dict, x, q):
    ein = lambda spec, a, b: product(  # noqa: E731
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), q)(a, b)
    h = _ln(x, p["attn_ln"]["scale"])
    a = p["attn"]
    qq = _rope(ein("bsh,hnd->bsnd", h, a["query"]["kernel"]),
               cfg["rope_theta"])
    kk = _rope(ein("bsh,hnd->bsnd", h, a["key"]["kernel"]),
               cfg["rope_theta"])
    vv = ein("bsh,hnd->bsnd", h, a["value"]["kernel"])
    s = qq.shape[1]
    scores = ein("bqnd,bknd->bnqk", qq, kk) * (qq.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    y = ein("bnqk,bknd->bqnd", probs, vv)
    x = x + ein("bsnd,ndh->bsh", y, a["out"]["kernel"])
    h = _ln(x, p["mlp_ln"]["scale"])
    h = jax.nn.gelu(ein("bsh,hf->bsf", h, p["up"]["kernel"]),
                    approximate=True)
    return x + ein("bsf,fh->bsh", h, p["down"]["kernel"])


def hidden(cfg: dict, params: dict, input_ids, *, quant: str | None = None):
    """The final LayerNorm's output ``[B, S, H]``."""
    x = params["embed"]["embedding"][input_ids]
    block = jax.checkpoint(lambda p, x: _block(cfg, p, x, quant))
    for i in range(cfg["num_layers"]):
        x = block(params[f"block_{i}"], x)
    return _ln(x, params["final_ln"]["scale"])


def _head(quant: str | None):
    return product(lambda a, b: jnp.einsum("...h,hv->...v", a, b,
                                           precision=HIGHEST), quant)


def forward(cfg: dict, params: dict, input_ids, *, quant: str | None = None):
    """Logits ``[B, S, V]`` in float32."""
    return _head(quant)(hidden(cfg, params, input_ids, quant=quant),
                        params["lm_head"]["kernel"])


def loss(cfg: dict, job: dict, params: dict, batch: dict,
         *, quant: str | None = None):
    """Mean next-token cross-entropy over the positions whose label is not
    -100 (the labels arrive already shifted).  The head and the softmax go
    row by row of the batch, each row recomputed in the backward pass, so
    that ``[S, V]`` logits are held and never ``[B, S, V]``."""
    x = hidden(cfg, params, batch["input_ids"], quant=quant)
    w = params["lm_head"]["kernel"]

    @jax.checkpoint
    def row(xr, labels):
        valid = labels != -100
        logp = jax.nn.log_softmax(_head(quant)(xr, w))
        tok = -jnp.take_along_axis(
            logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(tok * valid), jnp.sum(valid)

    total, count = lax.map(lambda a: row(*a), (x, batch["labels"]))
    return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)


# --------------------------------------------------------------------------
# the training step: clip by global norm, then AdamW
# --------------------------------------------------------------------------

B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def train_steps(cfg: dict, job: dict, params: dict, batches: list,
                *, quant: str | None = None, keep_rows: int | None = None):
    """Follow the job's first ``len(batches)`` steps from ``params``.

    Returns ``{"losses": [...], "opt_grad": tree, "delta": tree}``: each
    step's loss, the first gradient as the optimizer gets it (after the
    clip: Adam's first moment after one step over ``1 - b1``), and the
    parameters' change after all the steps."""
    wd, clip = float(job.get("weight_decay", 0.0)), job.get("grad_clip_norm")

    @jax.jit
    def step(params, mu, nu, batch, i):
        if keep_rows is not None:
            batch = {k: v[:keep_rows] for k, v in batch.items()}
        val, grads = jax.value_and_grad(
            lambda p: loss(cfg, job, p, batch, quant=quant))(params)
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

    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, val, grads = step(params, mu, nu, batch,
                                          jnp.asarray(i, jnp.float32))
        losses.append(float(val))
        if i == 0:
            first = grads
    delta = jax.tree.map(lambda a, b: a - b, params, start)
    return {"losses": losses, "opt_grad": first, "delta": delta}


# --------------------------------------------------------------------------
# serving: the gap of a served token below the reference's best
# --------------------------------------------------------------------------

def make_gap_fn(cfg: dict, *, quant: str | None = None):
    """A jitted ``(params, ids[1, T]) -> (gaps[T], control[T])``.

    ``gaps[t]`` is how far the float32 logit of the token that follows
    position ``t`` in ``ids`` lies below the best float32 logit there: 0
    where the served token is the reference's own choice.  ``control[t]``
    is the same for the token that ``quant`` arithmetic puts first at
    ``t`` (zeros without ``quant``).  Attention is causal, so whatever
    pads ``ids`` behind the served tokens changes nothing before it; the
    caller reads positions ``n_prompt - 1 .. n_total - 2``."""

    def fn(params, ids):
        ref = forward(cfg, params, ids)[0]
        best = jnp.max(ref, axis=-1)
        nxt = jnp.roll(ids[0], -1)
        gaps = best - jnp.take_along_axis(ref, nxt[:, None], axis=1)[:, 0]
        if quant is None:
            return gaps, jnp.zeros_like(gaps)
        low = forward(cfg, params, ids, quant=quant)[0]
        pick = jnp.argmax(low, axis=-1)
        return gaps, best - jnp.take_along_axis(ref, pick[:, None],
                                                axis=1)[:, 0]

    return jax.jit(fn)
