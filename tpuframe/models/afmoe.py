"""``afmoe``: a decoder whose layers alternate sliding-window and full
attention over grouped K/V heads, gate their attention output, and route
most of their MLPs over sigmoid-scored experts beside a shared one.

The block, for layer ``l`` of type ``layer_types[l]``::

    a = RMSNorm(x);  q, k, v, g = a.Wq, a.Wk, a.Wv, a.Wg
    q, k = RMSNorm_head(q), RMSNorm_head(k)        # per head, over head_dim
    q, k = rope(q), rope(k)                        # window layers only
    o = attention(q, k, v; causal, window on window layers, h reads h // G)
    x = x + RMSNorm((o * sigmoid(g)).Wo)           # a norm after, as before
    m = RMSNorm(x)
    f = SwiGLU_dense(m)                            # the first dense layers
      | shared(m) + sum_{e in top-k(s + b), e held here} w_e expert_e(m)
    x = x + RMSNorm(f)

with ``s = sigmoid(m.Wr)`` in float32 and ``w_e = route_scale * s_e / sum
of the picked s``; the bias ``b`` selects, does not weigh, and is a
parameter that takes no gradient (``router/bias``).  There is no auxiliary
loss.  The embedding is scaled by ``sqrt(hidden_size)``.

One chip's share of a wider deployment is a config, not a fork: a model
told ``experts_held`` (and ``expert_first``) routes over all
``num_experts`` and computes the picks for the experts it holds
(``ops/moe.py: routed_experts``); ``vocab_size`` is the vocabulary it
holds.  What a step routes is counted on the device in the
``moe_counters`` collection (``load`` over all experts, ``rows_here``,
``rows_absent``, ``tokens_routed``, a layer), which the harness keeps in
``TrainState.model_state`` and hands, through the model's
``publish_state`` hook, to :func:`publish_counters`:
``obs.metrics.counters("moe.")`` fetches them when it is read under that
prefix, never once a step and never for a reader that names no prefix.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpuframe import mem
from tpuframe.models.transformer_lm import rope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144        # the leading dense layers' MLP
    moe_intermediate_size: int = 1024    # each expert's, and the shared one's
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2
    # per layer "sliding_attention" | "full_attention"; empty: three window
    # layers to each full one
    layer_types: tuple = ()
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    embed_scale: bool = True             # x0 = Embed[ids] * sqrt(hidden)
    max_seq: int = 8192
    # this chip's share of the experts: [expert_first, expert_first + held)
    experts_held: int | None = None      # None: all of them
    expert_first: int = 0
    dtype: str = "float32"
    attn_impl: str | None = None
    remat: bool = False

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            FULL if (i + 1) % 4 == 0 else SLIDING
            for i in range(self.num_layers))
        if len(types) != self.num_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {types} does not name "
                             f"{self.num_layers} layers' attention")
        object.__setattr__(self, "layer_types", types)
        object.__setattr__(self, "experts_held", held_experts(
            self.num_experts, self.experts_held, self.expert_first))
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads over "
                             f"{self.num_kv_heads} K/V heads")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """The ratios at toy widths: 4 query heads over 2 K/V heads, a
        window of 16, 8 experts, 2 a token, 4 held, 1 dense layer and one
        period S, S, S, F."""
        base = dict(vocab_size=256, hidden_size=32, num_layers=5,
                    num_heads=4, num_kv_heads=2, head_dim=8,
                    intermediate_size=48, moe_intermediate_size=16,
                    num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
                    layer_types=(SLIDING,) * 4 + (FULL,), sliding_window=16,
                    experts_held=4, max_seq=64)
        base.update(kw)
        return cls(**base)


def held_experts(num_experts: int, held: int | None, first: int) -> int:
    """How many experts a chip that holds ``[first, first + held)`` of
    ``num_experts`` holds (``held`` None: all of them), or a ValueError."""
    held = num_experts if held is None else held
    if not 0 < held <= num_experts - first:
        raise ValueError(f"experts [{first}, {first + held}) are not among "
                         f"{num_experts}")
    return held


# The norm, the MLP and the expert layer below are also the ``deepseek_v3``
# block's (models/deepseek_v3.py): ``cfg`` is either decoder's config.


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (y * scale).astype(self.dtype)


class SwiGLU(nn.Module):
    width: int
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.cfg.jnp_dtype, name=name)
        h = nn.silu(dense(self.width, "gate")(x)) * dense(self.width, "up")(x)
        return dense(self.cfg.hidden_size, "down")(h)


class GatedAttention(nn.Module):
    cfg: AfmoeConfig
    window: bool

    @nn.compact
    def __call__(self, x, positions):
        from tpuframe.ops import attention as attn_ops

        c = self.cfg
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, c.head_dim), use_bias=False, dtype=c.jnp_dtype, name=name)
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.jnp_dtype,  # noqa: E731
                                    name=name)
        q = norm("q_norm")(heads(c.num_heads, "query")(x))
        k = norm("k_norm")(heads(c.num_kv_heads, "key")(x))
        v = heads(c.num_kv_heads, "value")(x)
        g = nn.Dense(c.num_heads * c.head_dim, use_bias=False,
                     dtype=c.jnp_dtype, name="gate")(x)
        if self.window:
            q = rope(q, positions, c.rope_theta)
            k = rope(k, positions, c.rope_theta)
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            o = attn_ops.multihead_attention(
                q, k, v, causal=True, impl=c.attn_impl,
                window=c.sliding_window if self.window else None)
        with jax.named_scope("attn.gate"):
            o = o.reshape(*o.shape[:-2], -1) * nn.sigmoid(g)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=c.jnp_dtype,
                        name="out")(o)


class Router(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens):
        from tpuframe.ops import moe as moe_ops

        c = self.cfg
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (c.hidden_size, c.num_experts))
        bias = self.param("bias", nn.initializers.zeros, (c.num_experts,))
        logits = jnp.dot(tokens.astype(jnp.float32), kernel)
        return moe_ops.route_sigmoid_topk(
            logits, bias, k=c.num_experts_per_tok, scale=c.route_scale,
            normalize=c.route_norm)


class Experts(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, idx, w):
        from tpuframe.ops import moe as moe_ops

        c = self.cfg
        held, h, i = c.experts_held, c.hidden_size, c.moe_intermediate_size
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        dtype = c.jnp_dtype
        gate, up = (self.param(n, init, (held, h, i)).astype(dtype)
                    for n in ("gate", "up"))
        down = self.param("down", init, (held, i, h)).astype(dtype)
        return moe_ops.routed_experts(
            tokens.astype(dtype), idx, w, gate, up, down,
            first=c.expert_first, num_experts=c.num_experts)


class MoE(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        from tpuframe.ops import moe as moe_ops

        c = self.cfg
        tokens = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe.route"):
            idx, w = Router(c, name="router")(tokens)
        y, plan = Experts(c, name="experts")(tokens, idx, w)
        if self.is_mutable_collection("moe_counters"):
            picks = idx.shape[0] * idx.shape[1]
            here = jnp.sum(plan.counts).astype(jnp.float32)
            for name, shape, add in (
                    ("load", (c.num_experts,),
                     moe_ops.expert_load(idx, c.num_experts)),
                    ("rows_here", (), here),
                    ("rows_absent", (), picks - here),
                    ("rows_looped", (), jnp.where(plan.fits, 0.0, here)),
                    ("tokens_routed", (), jnp.float32(picks))):
                var = self.variable("moe_counters", name, jnp.zeros, shape,
                                    jnp.float32)
                var.value = var.value + add
        y = y.reshape(x.shape)
        if c.num_shared_experts:
            y = y + SwiGLU(c.moe_intermediate_size * c.num_shared_experts,
                           c, name="shared")(x)
        return y


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    window: bool
    dense: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.jnp_dtype,  # noqa: E731
                                    name=name)
        h = GatedAttention(c, self.window, name="attn")(norm("attn_ln")(x),
                                                        positions)
        x = x + norm("attn_post_ln")(h)
        h = norm("mlp_ln")(x)
        if self.dense:
            h = SwiGLU(c.intermediate_size, c, name="mlp")(h)
        else:
            h = MoE(c, name="moe")(h)
        return x + norm("mlp_post_ln")(h)


class Afmoe(nn.Module):
    """input_ids [B, S] -> logits [B, S, V] (f32), or with
    ``hidden_only`` the final norm's output for the fused loss head."""

    cfg: AfmoeConfig = field(default_factory=AfmoeConfig)

    @staticmethod
    def publish_state(model_state) -> None:
        """``build_harness`` hands a model that has this hook every
        step's new ``model_state`` (a reference, never a transfer)."""
        publish_counters(model_state)

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 hidden_only: bool = False):
        c = self.cfg
        positions = jnp.arange(input_ids.shape[-1])
        x = nn.Embed(c.vocab_size, c.hidden_size, name="embed")(input_ids)
        if c.embed_scale:
            x = x * math.sqrt(c.hidden_size)
        x = mem.seam(x.astype(c.jnp_dtype), "embed_out")
        block = mem.remat_module(AfmoeBlock) if c.remat else AfmoeBlock
        for i, kind in enumerate(c.layer_types):
            x = block(c, kind == SLIDING, i < c.num_dense_layers,
                      name=f"block_{i}")(x, positions)
            x = mem.seam(x, "block_out")
        x = RMSNorm(c.rms_norm_eps, c.jnp_dtype, name="final_ln")(x)
        if hidden_only:
            return x
        logits = nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the counters, from the device to obs.metrics
# ---------------------------------------------------------------------------

_latest: list = [None]   # the newest step's ``moe_counters``, on the device


def publish_counters(model_state) -> None:
    """Keep a reference to a step's ``moe_counters`` (no transfer);
    ``obs.metrics.counters("moe.")`` fetches the newest when it is read."""
    counters = model_state.get("moe_counters") if isinstance(
        model_state, dict) else None
    if counters is None:
        return
    from tpuframe.obs import metrics

    _latest[0] = counters
    metrics.register_source("moe.", _read_counters)


def _read_counters() -> dict:
    if _latest[0] is None:
        return {}
    try:
        host = jax.device_get(_latest[0])
    except RuntimeError:   # donated to the next step before it was published
        return {}
    flat = jax.tree_util.tree_flatten_with_path(host)[0]
    out: dict = {}
    loads = []
    for path, val in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "load":
            loads.append(val)
        else:
            out[f"moe.{name}"] = out.get(f"moe.{name}", 0.0) + float(val)
    out = {name: round(total) for name, total in out.items()}
    if loads:
        worst = max(loads, key=lambda v: float(v.max()) / max(
            float(v.mean()), 1e-30))
        for e, n in enumerate(worst):
            out[f"moe.load.{e}"] = round(float(n))
        out["moe.layers"] = len(loads)
    return out
