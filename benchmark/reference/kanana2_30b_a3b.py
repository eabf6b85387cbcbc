"""Plain reference of the ``kanana2_30b_a3b`` configuration: one chip's
share of kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type``
``deepseek_v3``) in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision: forward, loss, gradients and the AdamW step.
Imports nothing of the program.

The layer, from the config's keys and the published ``deepseek_v3``
modeling code (the configuration file's ``assumed`` lists what the keys do
not settle): RMSNorm (eps 1e-6) before each sub-layer and none after; q one
product (``q_lora_rank`` null) to 32 heads of 192, split 128 | 64;
``kv_a_proj_with_mqa`` 2048 -> 512 + 64: a latent, RMSNormed and projected
up to 32 heads of 128 (keys) + 128 (values), and ONE rotary key a position
that every head reads; rotary positions (theta 1e6, adjacent pairs) on the
64 rope dims of q and on that key; scores ``(q_nope.k_nope + q_rope.r) /
sqrt(192)``, causal; no gate, no biases, no q/k norms; layer 0's MLP a
dense SwiGLU of 6144, the others 128 sigmoid-scored experts of 768, the
top 6 of ``s + bias`` picked, weighted by ``2.448 * s / (sum of the picked
s + 1e-20)`` (the bias selects, does not weigh and takes no gradient),
beside two shared experts that are one SwiGLU of 1536; the embedding
unscaled; the head untied; no auxiliary loss.

The share is Trinity-Mini's (``trinity_mini.py``, whose router, expert
loop, bias calibration, norm, SwiGLU, rotary positions and schedule this
module imports: one expert layer in the references as in the program): the
router scores all ``num_experts``, this chip adds the outputs of experts
``[expert_first, expert_first + experts_held)`` only, the vocabulary is
the slice held here.  ``init_weights`` calibrates each router's selection
bias on a seeded batch, layer by layer, until the fullest expert has at
most 1.1 times the mean load.  Two sizes of the seeded weights are set so
that such a bias can balance fresh sequences at all (``EMBED_STD``,
``ATTN_OUT_GAIN``): a random model's attention is a near-uniform average
over the prefix, a vector that all the tokens of one sequence share and
that differs from sequence to sequence.  With the embedding at the
initializer's 0.02 that average is as large as the token's own row, and
through an output projection at full gain it grows layer by layer; each
sequence then carries a routing skew of its own that no static bias
balances (read on the chip, PERF.md section 6: 5.28 times the mean).  With
unit embedding rows and the projection at a quarter gain (as depth-scaled
and zero-init residual branches start) the token decides its experts: 1.16
to 1.24 on fresh sequences, layer 1 to layer 5 alike.

``quant="int8"`` and ``keep_rows`` are the control and the planted fault,
as in ``trinity_mini.py``.  Every block is recomputed in the backward pass,
attention goes by groups of heads and blocks of queries and the loss head
by blocks of positions, and the step's buffers are donated, so that float32 at 8192 tokens fits
one chip.
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
import _adamw  # noqa: E402
from _adamw import B1  # noqa: E402,F401  (the runner reads it)
from _common import nest  # noqa: E402
from trinity_mini import (CALIBRATION_IDS, COUNTERS, HEAD_BLOCK,  # noqa: E402
                          QUERY_BLOCK, _ein, _rms, _rope, _scores, _swiglu,
                          balance_bias, learning_rate, route, routed)

HEAD_GROUP = 8         # heads whose q, k, v are live at once
EMBED_STD = 1.0        # the embedding's rows start at unit scale, and
ATTN_OUT_GAIN = 0.25   # attention's output projection as a small gain


def _names(cfg: dict) -> list[tuple[str, tuple]]:
    h, n = cfg["hidden_size"], cfg["num_heads"]
    d_nope, d_rope, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"])
    rank, mi = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    e, held = cfg["num_experts"], cfg["experts_held"]
    shared = mi * cfg["num_shared_experts"]
    out = [("embed/embedding", (cfg["vocab_size"], h))]
    for i in range(cfg["num_layers"]):
        b = f"block_{i}"
        out += [(f"{b}/attn_ln/scale", (h,)),
                (f"{b}/attn/query/kernel", (h, n, d_nope + d_rope)),
                (f"{b}/attn/kv_a/kernel", (h, rank + d_rope)),
                (f"{b}/attn/kv_a_ln/scale", (rank,)),
                (f"{b}/attn/kv_b/kernel", (rank, n, d_nope + d_v)),
                (f"{b}/attn/out/kernel", (n * d_v, h)),
                (f"{b}/mlp_ln/scale", (h,))]
        if not _is_moe(cfg, i):
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
                    (f"{b}/moe/shared/gate/kernel", (h, shared)),
                    (f"{b}/moe/shared/up/kernel", (h, shared)),
                    (f"{b}/moe/shared/down/kernel", (shared, h))]
    out += [("final_ln/scale", (h,)),
            ("lm_head/kernel", (h, cfg["vocab_size"]))]
    return out


def _is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def init_weights(cfg: dict, seed: int) -> dict:
    """``{"params": ..., "model_state": ...}`` from the seed, float32, made
    on the device: kernels normal with variance 1/fan_in (attention's
    output projection ``ATTN_OUT_GAIN`` times that), the embedding normal
    ``EMBED_STD``, norm scales 1 + 0.1 normal, each router's bias
    calibrated on a seeded batch (module docstring); ``model_state`` is the program's
    routing counters at zero."""
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
            elif leaf == "bias":
                val = jnp.zeros(shape)
            elif leaf == "embedding":
                val = EMBED_STD * jax.random.normal(k, shape)
            else:
                fan_in = shape[-2] if "/experts/" in path else shape[0]
                val = jax.random.normal(k, shape) / math.sqrt(fan_in)
                if path.endswith("/attn/out/kernel"):
                    val = ATTN_OUT_GAIN * val
            flat[path] = val.astype(jnp.float32)
        ids = jax.random.randint(
            jax.random.fold_in(key, len(names)),
            (max(1, CALIBRATION_IDS // cfg["max_seq"]), cfg["max_seq"]), 0,
            cfg["vocab_size"])
        return _calibrated(cfg, nest(flat), ids)

    return jax.jit(make)


def _calibrated(cfg: dict, params: dict, ids):
    """``params`` with every router's bias balanced on ``ids``, layer by
    layer: each layer sees what the balanced layers before it put out."""
    x = params["embed"]["embedding"][ids]
    for i in range(cfg["num_layers"]):
        p = params[f"block_{i}"]
        if _is_moe(cfg, i):
            _, m = _attention_half(cfg, p, x, None)
            s = _scores(p, m.reshape(-1, m.shape[-1]), None)
            p = dict(p, moe=dict(p["moe"], router=dict(
                p["moe"]["router"], bias=balance_bias(cfg, s))))
            params = dict(params, **{f"block_{i}": p})
        x = _block(cfg, i, p, x, None)
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def score_scale(cfg: dict) -> float:
    """One over the root of the whole score width, 128 + 64: not of the
    head's own 128, nor of the config's ``head_dim`` (64, the rope width)."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def _attention(cfg: dict, a: dict, h, q):
    """Latent attention of normed input ``h [B, S, H]``.  The latent and
    the rotary key are made once; the heads then go ``HEAD_GROUP`` at a
    time (their slices of Wq, Wkvb and Wo; each group recomputed in the
    backward pass) and each group's queries ``QUERY_BLOCK`` at a time, so
    that at 8192 tokens the float32 q, k, v of 32 heads and their scores
    are never live at once.  Heads do not see each other: the sum of the
    groups' outputs is the layer's."""
    b, s, _ = h.shape
    n, d_nope, rank = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                       cfg["kv_lora_rank"])
    kv_a = _ein("bsh,hf->bsf", q)(h, a["kv_a"]["kernel"])
    latent = _rms(cfg, kv_a[..., :rank], a["kv_a_ln"]["scale"])
    # ONE rotary key a position, rotated once, read by every head
    r = _rope(kv_a[..., None, rank:], cfg["rope_theta"])[:, :, 0]
    scale, blk = score_scale(cfg), min(QUERY_BLOCK, s)
    g = min(HEAD_GROUP, n)

    @jax.checkpoint
    def heads(y, w):
        wq, wkvb, wo = w
        qq = _ein("bsh,hnd->bsnd", q)(h, wq)
        kv = _ein("bsr,rnd->bsnd", q)(latent, wkvb)
        q_nope = qq[..., :d_nope]
        q_rope = _rope(qq[..., d_nope:], cfg["rope_theta"])
        k_nope, v = kv[..., :d_nope], kv[..., d_nope:]

        @jax.checkpoint
        def rows(qn, qr, row0):
            keep = jnp.arange(s)[None, :] <= row0 + jnp.arange(blk)[:, None]
            sc = (_ein("bqnd,bknd->bnqk", q)(qn, k_nope)
                  + _ein("bqnd,bkd->bnqk", q)(qr, r)) * scale
            pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return _ein("bnqk,bknd->bqnd", q)(pr, v)

        split = lambda t: t.reshape(  # noqa: E731
            b, s // blk, blk, *t.shape[2:]).swapaxes(0, 1)
        o = lax.map(lambda args: rows(*args),
                    (split(q_nope), split(q_rope), jnp.arange(0, s, blk)))
        o = o.swapaxes(0, 1).reshape(b, s, -1)
        return y + _ein("bsf,fh->bsh", q)(o, wo), None

    by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
        w.reshape(*w.shape[:axis], n // g, g, *w.shape[axis + 1:]), axis, 0)
    wo = a["out"]["kernel"]
    y, _ = lax.scan(heads, jnp.zeros_like(h), (
        by_group(a["query"]["kernel"], 1), by_group(a["kv_b"]["kernel"], 1),
        wo.reshape(n // g, -1, wo.shape[-1])))
    return y


def _attention_half(cfg: dict, p: dict, x, q):
    """``(x after attention, the MLP's normed input)``."""
    x = x + _attention(cfg, p["attn"], _rms(cfg, x, p["attn_ln"]["scale"]),
                       q)
    return x, _rms(cfg, x, p["mlp_ln"]["scale"])


def _mlp(cfg: dict, i: int, p: dict, m, q):
    if not _is_moe(cfg, i):
        return _swiglu(p["mlp"], m, q)
    tokens = m.reshape(-1, m.shape[-1])
    idx, w = route(cfg, p, tokens, q)
    return routed(cfg, p, tokens, idx, w, q).reshape(m.shape) \
        + _swiglu(p["moe"]["shared"], m, q)


def _block(cfg: dict, i: int, p: dict, x, q):
    """The two halves are recomputed apart in the backward pass, so that
    the 32 heads' float32 q, k, v and the MLP's hidden rows are never live
    together (the six copies of the weights leave about 5 GB)."""
    x, m = jax.checkpoint(lambda p, x: _attention_half(cfg, p, x, q))(p, x)
    return x + jax.checkpoint(lambda p, m: _mlp(cfg, i, p, m, q))(p, m)


def hidden(cfg: dict, params: dict, input_ids, *, quant: str | None = None):
    """The final norm's output ``[B, S, H]``."""
    x = params["embed"]["embedding"][input_ids]
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


def train_steps(cfg: dict, job: dict, params: dict, batches: list,
                *, quant: str | None = None, keep_rows: int | None = None):
    """The job's first ``len(batches)`` steps from ``params``
    (``_adamw.train_steps``: clip by global norm, then AdamW)."""
    return _adamw.train_steps(
        lambda p, batch: loss(cfg, job, p, batch, quant=quant),
        learning_rate, job, params, batches, keep_rows=keep_rows)
