"""Plain reference of the ``trinity_mini`` configuration: one chip's share
of arcee-ai/Trinity-Mini (``model_type`` ``afmoe``) in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision: forward, loss,
gradients and the AdamW step.  Imports nothing of the program.

The layer, as the configuration file's ``assumed`` lists it from the
published modeling code: RMSNorm before and after each sub-layer; per-head
RMSNorm on q and k; rotary positions (adjacent pairs, theta 10000) on the
sliding-window layers only; 32 query heads over 4 K/V heads, query head
``h`` reading K/V head ``h // 8``; key ``j`` visible to query ``i`` iff ``0
<= i - j`` and, on a window layer, ``i - j < sliding_window``; the
attention output gated by ``sigmoid(a Wg)``; SwiGLU MLPs; sigmoid router
scores in float32, the top 8 of ``s + bias`` picked, weighted by ``2.826 *
s / sum of the picked s`` (the bias selects, does not weigh and takes no
gradient); a shared expert beside the routed ones; the embedding scaled by
``sqrt(hidden)``; no auxiliary loss.

The share: the router scores all ``num_experts``; this chip holds experts
``[expert_first, expert_first + experts_held)`` and adds only their
outputs (``sum over shares + the shared expert once`` is the uncut layer,
which a tier-1 test holds it to); the vocabulary is the slice held here.

``init_weights`` also makes the state a trained router is in: the
selection bias that balances the load, by the published rule ``b += 0.001
* sign(mean load - load)`` on a seeded calibration batch, layer by layer,
until the fullest expert has at most 1.1 times the mean load.  The norm
after each sub-layer starts as a small gain (``POST_NORM_SCALE``): at 1 a
random model's post-norm blows the attention's near-uniform average over
the prefix, noise whose direction differs by sequence, up to the size of
the residual stream, and each sequence then has a routing skew of its own
that no bias balances (read on the chip, PERF.md section 6: at 1 the load
is 4.1-5.8 times the mean, the dropless layer's buffer overflows on three
seeds of five and the rate spreads 1.1% over the seeds; at 0.02, 1.10-1.18
and 0.06%).  The price: with sub-layers this quiet the losses hardly see
the products' precision, so the gradient numbers, not the losses, tell the
int8 control from the program in this cell.

``quant="int8"`` computes every matrix product on operands rounded to 8-bit
integers (the control that has to come out as not correct); ``keep_rows``
is the planted fault "part of the batch left out" (of a one-row batch: the
second half of the sequence).  Every block is recomputed in the backward
pass, attention goes by blocks of queries and the head by blocks of
positions, and the step's buffers are donated, so that float32 at 8192
tokens fits one chip.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import decays, nest, product  # noqa: E402

HIGHEST = lax.Precision.HIGHEST
SLIDING = "sliding_attention"
QUERY_BLOCK = 512      # queries whose scores are live at once
HEAD_BLOCK = 2048      # positions whose logits are live at once
BIAS_RATE = 0.001      # the published bias update's step
BALANCED = 1.1         # fullest expert over the mean, at most
MAX_ROUNDS = 4000
POST_NORM_SCALE = 0.02  # the norm after a sub-layer starts as a small gain
CALIBRATION_IDS = 8192  # in whole sequences of the configuration's length
COUNTERS = (("load", None), ("rows_here", ()), ("rows_absent", ()),
            ("rows_looped", ()), ("tokens_routed", ()))


def _names(cfg: dict) -> list[tuple[str, tuple]]:
    h, n, nkv, d = (cfg["hidden_size"], cfg["num_heads"],
                    cfg["num_kv_heads"], cfg["head_dim"])
    e, held, mi = (cfg["num_experts"], cfg["experts_held"],
                   cfg["moe_intermediate_size"])
    out = [("embed/embedding", (cfg["vocab_size"], h))]
    for i in range(cfg["num_layers"]):
        b = f"block_{i}"
        out += [(f"{b}/attn_ln/scale", (h,)),
                (f"{b}/attn/query/kernel", (h, n, d)),
                (f"{b}/attn/q_norm/scale", (d,)),
                (f"{b}/attn/key/kernel", (h, nkv, d)),
                (f"{b}/attn/k_norm/scale", (d,)),
                (f"{b}/attn/value/kernel", (h, nkv, d)),
                (f"{b}/attn/gate/kernel", (h, n * d)),
                (f"{b}/attn/out/kernel", (n * d, h)),
                (f"{b}/attn_post_ln/scale", (h,)),
                (f"{b}/mlp_ln/scale", (h,))]
        if i < cfg["num_dense_layers"]:
            ff = cfg["intermediate_size"]
            out += [(f"{b}/mlp/gate/kernel", (h, ff)),
                    (f"{b}/mlp/up/kernel", (h, ff)),
                    (f"{b}/mlp/down/kernel", (ff, h))]
        else:
            out += [(f"{b}/moe/router/kernel", (h, e)),
                    (f"{b}/moe/router/bias", (e,)),
                    (f"{b}/moe/experts/gate", (held, h, mi)),
                    (f"{b}/moe/experts/up", (held, h, mi)),
                    (f"{b}/moe/experts/down", (held, mi, h)),
                    (f"{b}/moe/shared/gate/kernel", (h, mi)),
                    (f"{b}/moe/shared/up/kernel", (h, mi)),
                    (f"{b}/moe/shared/down/kernel", (mi, h))]
        out += [(f"{b}/mlp_post_ln/scale", (h,))]
    out += [("final_ln/scale", (h,)),
            ("lm_head/kernel", (h, cfg["vocab_size"]))]
    return out


def _is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def init_weights(cfg: dict, seed: int) -> dict:
    """``{"params": ..., "model_state": ...}`` from the seed, float32, made
    on the device: kernels normal with variance 1/fan_in, the embedding
    normal 0.02, scales near 1, each router's bias calibrated on a seeded
    batch (module docstring); ``model_state`` is the program's routing
    counters at zero."""
    params = _make_weights(json.dumps(cfg, sort_keys=True))(
        jax.random.key(seed % (2 ** 31 - 1)))
    counters = {
        f"block_{i}": {"moe": {
            name: jnp.zeros((cfg["num_experts"],) if shape is None else shape,
                            jnp.float32) for name, shape in COUNTERS}}
        for i in range(cfg["num_layers"]) if _is_moe(cfg, i)}
    return {"params": params, "model_state": {"moe_counters": counters}}


@functools.lru_cache(maxsize=4)
def _make_weights(cfg_json: str):
    """The jitted maker of one configuration's weights, kept: a run asks
    for the same weights three times and should trace it once."""
    cfg = json.loads(cfg_json)
    names = _names(cfg)

    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(names):
            k = jax.random.fold_in(key, i)
            leaf = path.rsplit("/", 1)[-1]
            if leaf == "scale":
                val = 1.0 + 0.1 * jax.random.normal(k, shape)
                if "_post_ln/" in path:
                    val = POST_NORM_SCALE * val
            elif leaf == "bias":
                val = jnp.zeros(shape)
            elif leaf == "embedding":
                val = 0.02 * jax.random.normal(k, shape)
            else:
                fan_in = shape[-2] if "/experts/" in path else shape[0]
                val = jax.random.normal(k, shape) / math.sqrt(fan_in)
            flat[path] = val.astype(jnp.float32)
        params = nest(flat)
        ids = jax.random.randint(
            jax.random.fold_in(key, len(names)),
            (max(1, CALIBRATION_IDS // cfg["max_seq"]), cfg["max_seq"]), 0,
            cfg["vocab_size"])
        return _calibrated(cfg, params, ids)

    return jax.jit(make)


def _calibrated(cfg: dict, params: dict, ids):
    """``params`` with every router's bias balanced on ``ids``, layer by
    layer: each layer sees what the balanced layers before it put out."""
    x = _embed(cfg, params, ids)
    for i in range(cfg["num_layers"]):
        p = params[f"block_{i}"]
        if _is_moe(cfg, i):
            _, m = _attention_half(cfg, i, p, x, None)
            s = _scores(p, m.reshape(-1, m.shape[-1]), None)
            p = dict(p, moe=dict(p["moe"], router=dict(
                p["moe"]["router"], bias=balance_bias(cfg, s))))
            params = dict(params, **{f"block_{i}": p})
        x = _block(cfg, i, p, x, None)
    return params


def balance_bias(cfg: dict, s):
    """The bias that balances scores ``s [T, E]``: ``b += rate * sign(mean
    load - load)`` until the fullest expert has at most ``BALANCED`` times
    the mean load (at most ``MAX_ROUNDS`` rounds)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    mean = s.shape[0] * k / e

    def load(b):
        _, idx = lax.top_k(s + b, k)
        return jnp.sum(idx.reshape(-1, 1) == jnp.arange(e)[None, :], axis=0)

    def go(state):
        b, c, n = state
        b = b + BIAS_RATE * jnp.sign(mean - c)
        return b, load(b), n + 1

    b0 = jnp.zeros((e,), jnp.float32)
    b, _, _ = lax.while_loop(
        lambda st: jnp.logical_and(jnp.max(st[1]) > BALANCED * mean,
                                   st[2] < MAX_ROUNDS),
        go, (b0, load(b0), 0))
    return b


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _ein(spec: str, q):
    return product(lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST), q)


def _rms(cfg: dict, x, scale):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + cfg["rms_norm_eps"]) * scale


def _rope(x, theta: float):
    """x ``[B, S, N, D]``; adjacent pairs ``(2i, 2i+1)`` rotate together."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _swiglu(p: dict, x, q):
    g = _ein("...h,hf->...f", q)(x, p["gate"]["kernel"])
    u = _ein("...h,hf->...f", q)(x, p["up"]["kernel"])
    return _ein("...f,fh->...h", q)(jax.nn.silu(g) * u, p["down"]["kernel"])


def _attention(cfg: dict, a: dict, h, window: int | None, q):
    b, s, _ = h.shape
    n, nkv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    proj = _ein("bsh,hnd->bsnd", q)
    qq = _rms(cfg, proj(h, a["query"]["kernel"]), a["q_norm"]["scale"])
    kk = _rms(cfg, proj(h, a["key"]["kernel"]), a["k_norm"]["scale"])
    vv = proj(h, a["value"]["kernel"])
    gate = _ein("bsh,hf->bsf", q)(h, a["gate"]["kernel"])
    if window is not None:
        qq, kk = _rope(qq, cfg["rope_theta"]), _rope(kk, cfg["rope_theta"])
    qq = qq.reshape(b, s, nkv, n // nkv, d)     # head h reads K/V head h // G
    blk = min(QUERY_BLOCK, s)
    # the keys a block of queries is scored against: all of them, or, on a
    # window layer, the stretch that holds every key its rows can see (the
    # rest would be masked anyway; the mask below is the definition)
    span = s if window is None else min(s, -(-(window + blk) // blk) * blk)

    @jax.checkpoint
    def rows(q_blk, row0):
        col0 = jnp.clip(row0 + blk - span, 0, s - span)
        k_blk = lax.dynamic_slice_in_dim(kk, col0, span, axis=1)
        v_blk = lax.dynamic_slice_in_dim(vv, col0, span, axis=1)
        i = row0 + jnp.arange(blk)[:, None]
        cols = col0 + jnp.arange(span)[None, :]
        keep = cols <= i
        if window is not None:
            keep = jnp.logical_and(keep, i - cols < window)
        sc = _ein("bqngd,bknd->bngqk", q)(q_blk, k_blk) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return _ein("bngqk,bknd->bqngd", q)(pr, v_blk)

    o = lax.map(lambda args: rows(*args),
                (qq.reshape(b, s // blk, blk, nkv, n // nkv, d)
                 .transpose(1, 0, 2, 3, 4, 5), jnp.arange(0, s, blk)))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, n * d)
    return _ein("bsf,fh->bsh", q)(o * jax.nn.sigmoid(gate),
                                  a["out"]["kernel"])


def _attention_half(cfg: dict, i: int, p: dict, x, q):
    """``(x after attention, the MLP's normed input)``."""
    window = cfg["sliding_window"] if cfg["layer_types"][i] == SLIDING \
        else None
    y = _attention(cfg, p["attn"], _rms(cfg, x, p["attn_ln"]["scale"]),
                   window, q)
    x = x + _rms(cfg, y, p["attn_post_ln"]["scale"])
    return x, _rms(cfg, x, p["mlp_ln"]["scale"])


def _scores(p: dict, m, q):
    return jax.nn.sigmoid(_ein("th,he->te", q)(
        m, p["moe"]["router"]["kernel"]))


def route(cfg: dict, p: dict, m, q):
    """``(idx [T, k], w [T, k])`` for tokens ``m [T, H]``."""
    s = _scores(p, m, q)
    bias = lax.stop_gradient(p["moe"]["router"]["bias"])
    _, idx = lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["route_scale"]


def routed(cfg: dict, p: dict, m, idx, w, q):
    """The held experts' part: a loop over them, each over every token,
    weighted by what the token gave it (nought where it was not picked)."""
    ex = p["moe"]["experts"]

    @jax.checkpoint
    def one(y, args):
        e, gate, up, down = args
        we = jnp.sum(jnp.where(idx == cfg["expert_first"] + e, w, 0.0),
                     axis=-1)
        hid = jax.nn.silu(_ein("th,hf->tf", q)(m, gate)) \
            * _ein("th,hf->tf", q)(m, up)
        return y + we[:, None] * _ein("tf,fh->th", q)(hid, down), None

    y, _ = lax.scan(one, jnp.zeros_like(m),
                    (jnp.arange(ex["gate"].shape[0]), ex["gate"], ex["up"],
                     ex["down"]))
    return y


def _block(cfg: dict, i: int, p: dict, x, q):
    x, m = _attention_half(cfg, i, p, x, q)
    if not _is_moe(cfg, i):
        f = _swiglu(p["mlp"], m, q)
    else:
        tokens = m.reshape(-1, m.shape[-1])
        idx, w = route(cfg, p, tokens, q)
        f = routed(cfg, p, tokens, idx, w, q).reshape(m.shape)
        if cfg.get("num_shared_experts", 1):
            f = f + _swiglu(p["moe"]["shared"], m, q)
    return x + _rms(cfg, f, p["mlp_post_ln"]["scale"])


def _embed(cfg: dict, params: dict, input_ids):
    return params["embed"]["embedding"][input_ids] \
        * math.sqrt(cfg["hidden_size"])


def hidden(cfg: dict, params: dict, input_ids, *, quant: str | None = None):
    """The final norm's output ``[B, S, H]``."""
    x = _embed(cfg, params, input_ids)
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(lambda p, x, i=i: _block(cfg, i, p, x, quant))(
            params[f"block_{i}"], x)
    return _rms(cfg, x, params["final_ln"]["scale"])


def forward(cfg: dict, params: dict, input_ids, *, quant: str | None = None):
    """Logits ``[B, S, V]`` in float32."""
    return _ein("...h,hv->...v", quant)(
        hidden(cfg, params, input_ids, quant=quant),
        params["lm_head"]["kernel"])


def loss(cfg: dict, job: dict, params: dict, batch: dict,
         *, quant: str | None = None):
    """Mean next-token cross-entropy over the positions whose label is not
    -100 (the labels arrive already shifted), the head and the softmax by
    blocks of positions, each recomputed in the backward pass."""
    x = hidden(cfg, params, batch["input_ids"], quant=quant)
    w = params["lm_head"]["kernel"]
    blk = min(HEAD_BLOCK, x.shape[1])

    @jax.checkpoint
    def part(xr, labels):
        valid = labels != -100
        logp = jax.nn.log_softmax(_ein("...h,hv->...v", quant)(xr, w))
        tok = -jnp.take_along_axis(
            logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(tok * valid), jnp.sum(valid)

    total, count = lax.map(
        lambda a: part(*a),
        (x.reshape(-1, blk, x.shape[-1]), batch["labels"].reshape(-1, blk)))
    return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)


# --------------------------------------------------------------------------
# the training step: clip by global norm, then AdamW
# --------------------------------------------------------------------------

B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def learning_rate(job: dict, step):
    """The job's schedule at ``step``: linear from 0 to the peak over
    ``warmup_steps``, then constant or a cosine to zero over the rest of
    ``total_steps`` (``utils/optim.py: lr_schedule``, which a tier-1 test
    pins this to)."""
    peak = job["base_lr"] * (job["global_batch"] / 256.0
                             if job.get("scale_lr_by_batch", True) else 1.0)
    warm = int(job.get("warmup_steps", 0))
    after = jnp.maximum(step - warm, 0.0)
    if job.get("schedule", "cosine") == "constant":
        rate = peak
    else:
        frac = jnp.minimum(after / max(job["total_steps"] - warm, 1), 1.0)
        rate = peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    if warm > 0:
        rate = jnp.where(step < warm, peak * step / warm, rate)
    return rate


def train_steps(cfg: dict, job: dict, params: dict, batches: list,
                *, quant: str | None = None, keep_rows: int | None = None):
    """Follow the job's first ``len(batches)`` steps from ``params``.

    Returns ``{"losses": [...], "opt_grad": tree, "delta": tree}``: each
    step's loss, the first gradient as the optimizer gets it (after the
    clip), and the parameters' change after all the steps."""
    wd, clip = float(job.get("weight_decay", 0.0)), job.get("grad_clip_norm")

    def step(params, mu, nu, batch, i):
        if keep_rows is not None:
            if batch["input_ids"].shape[0] > 1:
                batch = {k: v[:keep_rows] for k, v in batch.items()}
            else:   # a batch of one row: its second half left out
                half = batch["input_ids"].shape[1] // 2
                batch = dict(batch, labels=batch["labels"].at[:, half:]
                             .set(-100))
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
