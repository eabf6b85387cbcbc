"""``deepseek_v3``: a decoder whose attention projects keys and values up
from a narrow latent, scores a head's own 128 dims beside 64 rotary dims
whose key all heads share, and whose MLPs, after a dense first layer, are
``afmoe``'s expert layer: sigmoid-scored routed experts beside shared ones.

The block (pre-norm only; no biases, no q/k norms, no gate)::

    a = RMSNorm(x)
    q = a.Wq                     -> [T, N, d_nope + d_rope]   split nope | rope
    c | r = a.Wkva               -> [T, kv_lora_rank] | [T, d_rope]
    kv = RMSNorm(c).Wkvb         -> [T, N, d_nope + d_v]      split k_nope | v
    q_rope, r = rope(q_rope), rope(r)        # r: ONE key a position, all heads
    s_h[i,j] = (q_nope_h[i].k_nope_h[j] + q_rope_h[i].r[j])
               / sqrt(d_nope + d_rope),  j <= i
    x = x + concat_h(softmax_j(s_h) v_h).Wo
    m = RMSNorm(x)
    x = x + (SwiGLU_dense(m)                           # the first dense layers
             | shared(m) + sum_{e in top-k(s + b), e held here} w_e expert_e(m))

The expert layer, its router (``w_e = route_scale * s_e / sum of the picked
s``; the bias selects and takes no gradient), the norms, SwiGLU and the
``moe_counters`` collection with its ``publish_state`` hook are
:mod:`tpuframe.models.afmoe`'s, imported: ``num_shared_experts`` shared
experts are one SwiGLU of that many times the expert width.  One chip's
share is a config, as there: ``experts_held``/``expert_first``/
``vocab_size``.  The embedding is not scaled; the head is untied.

Field names are the repo's where it has one (``num_experts`` is the
source's ``n_routed_experts``, ``num_shared_experts`` its
``n_shared_experts``, ``num_dense_layers`` its ``first_k_dense_replace``,
``route_norm`` its ``norm_topk_prob``, ``route_scale`` its
``routed_scaling_factor``) and the source's where it has none.  The
source's ``q_lora_rank`` is null in the models this serves: q is one
product.  What serving this block needs, a latent cache and the absorbed
decode path, is not here.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpuframe import mem
from tpuframe.models import afmoe
from tpuframe.models.afmoe import MoE, RMSNorm, SwiGLU
from tpuframe.models.transformer_lm import rope


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144        # the leading dense layers' MLP
    moe_intermediate_size: int = 768     # each expert's
    num_experts: int = 128
    num_experts_per_tok: int = 6
    num_shared_experts: int = 2
    num_dense_layers: int = 1
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    route_norm: bool = True
    route_scale: float = 2.448
    max_seq: int = 8192
    # this chip's share of the experts: [expert_first, expert_first + held)
    experts_held: int | None = None      # None: all of them
    expert_first: int = 0
    dtype: str = "float32"
    attn_impl: str | None = None
    remat: bool = False

    def __post_init__(self):
        object.__setattr__(self, "experts_held", afmoe.held_experts(
            self.num_experts, self.experts_held, self.expert_first))

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV3Config":
        """The ratios at toy widths: 4 heads of 16 + 8 against values of
        16, a latent of 32, 8 experts, 2 a token, 4 held, 2 shared, one
        dense layer and two expert layers."""
        base = dict(vocab_size=256, hidden_size=32, num_layers=3, num_heads=4,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    kv_lora_rank=32, intermediate_size=48,
                    moe_intermediate_size=8, num_experts=8,
                    num_experts_per_tok=2, num_shared_experts=2,
                    num_dense_layers=1, experts_held=4, max_seq=64)
        base.update(kw)
        return cls(**base)


class LatentAttention(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, positions):
        from tpuframe.ops import attention as attn_ops

        c = self.cfg
        d_nope, d_rope, rank = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                                c.kv_lora_rank)
        heads = lambda width, name: nn.DenseGeneral(  # noqa: E731
            (c.num_heads, width), use_bias=False, dtype=c.jnp_dtype,
            name=name)
        q = heads(d_nope + d_rope, "query")(x)
        kv_a = nn.Dense(rank + d_rope, use_bias=False, dtype=c.jnp_dtype,
                        name="kv_a")(x)
        with jax.named_scope("attn.latent.kv_up"):
            kv = heads(d_nope + c.v_head_dim, "kv_b")(
                RMSNorm(c.rms_norm_eps, c.jnp_dtype, name="kv_a_ln")(
                    kv_a[..., :rank]))
        # the rotary key: rotated once, one row a position for every head
        k_rope = rope(kv_a[..., None, rank:], positions, c.rope_theta)
        q_rope = rope(q[..., d_nope:], positions, c.rope_theta)
        with jax.named_scope("attn.latent"):
            o = attn_ops.multihead_attention(
                q[..., :d_nope], kv[..., :d_nope], kv[..., d_nope:],
                rope=(q_rope, k_rope), causal=True, impl=c.attn_impl)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=c.jnp_dtype,
                        name="out")(o.reshape(*o.shape[:-2], -1))


class DeepseekV3Block(nn.Module):
    cfg: DeepseekV3Config
    dense: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.jnp_dtype,  # noqa: E731
                                    name=name)
        x = x + LatentAttention(c, name="attn")(norm("attn_ln")(x), positions)
        h = norm("mlp_ln")(x)
        if self.dense:
            return x + SwiGLU(c.intermediate_size, c, name="mlp")(h)
        return x + MoE(c, name="moe")(h)


class DeepseekV3(nn.Module):
    """input_ids [B, S] -> logits [B, S, V] (f32), or with
    ``hidden_only`` the final norm's output for the fused loss head."""

    cfg: DeepseekV3Config = field(default_factory=DeepseekV3Config)

    @staticmethod
    def publish_state(model_state) -> None:
        afmoe.publish_counters(model_state)

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 hidden_only: bool = False):
        c = self.cfg
        positions = jnp.arange(input_ids.shape[-1])
        x = nn.Embed(c.vocab_size, c.hidden_size, name="embed")(input_ids)
        x = mem.seam(x.astype(c.jnp_dtype), "embed_out")
        block = mem.remat_module(DeepseekV3Block) if c.remat \
            else DeepseekV3Block
        for i in range(c.num_layers):
            x = block(c, i < c.num_dense_layers, name=f"block_{i}")(
                x, positions)
            x = mem.seam(x, "block_out")
        x = RMSNorm(c.rms_norm_eps, c.jnp_dtype, name="final_ln")(x)
        if hidden_only:
            return x
        logits = nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        return logits.astype(jnp.float32)
