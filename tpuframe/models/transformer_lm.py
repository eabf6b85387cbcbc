"""Decoder-only causal transformer LM — the long-context workload.

Beyond the reference's capability bar (its longest sequence is BERT-base
GLUE at 512 tokens — SURVEY.md §5.7): this model exists to exercise the
framework's first-class long-context path.  Architecture is the standard
modern decoder: pre-LN, RoPE, GELU MLP, untied LM head, bf16-compute capable.

Sequence parallelism is a *model config*, not a code fork: with
``seq_mode="ring"`` or ``"ulysses"`` the attention core runs the
sequence-parallel kernels from :mod:`tpuframe.ops.seq_parallel` over the
mesh's ``seq`` axis, and RoPE positions are offset by the device's global
chunk position (``lax.axis_index``).  Outside shard_map (or with the seq
axis unbound / size 1) the same model falls back to full attention — the
laptop-to-pod property the framework keeps everywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpuframe import mem


@dataclass(frozen=True)
class LMConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq: int = 8192
    dropout: float = 0.0
    rope_theta: float = 10000.0
    dtype: str = "float32"          # "bfloat16" for MXU throughput
    attn_impl: str | None = None    # None → TPUFRAME_ATTN_IMPL env / xla
    seq_axis: str = "seq"
    seq_mode: str = "none"          # none | ring | ulysses
    remat: bool = False             # jax.checkpoint each block (long-context)
    # Mixture of experts (expert parallelism over the ``expert`` mesh axis;
    # weights placed by tpuframe.parallel.tp rules). 0 experts = dense.
    moe_experts: int = 0
    moe_every: int = 2              # every Nth block swaps MLP for MoE
    moe_k: int = 2                  # experts per token
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01    # load-balance loss weight (harness adds)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "LMConfig":
        base = dict(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128, max_seq=512)
        base.update(kw)
        return cls(**base)


def _seq_axis_bound(name: str) -> bool:
    try:
        lax.axis_size(name)
    except NameError:
        return False
    return True


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding. x: [B, S, N, D]; positions: [S] global,
    or [B, S] per-sequence (the decode path: each batch slot sits at its
    own absolute position in its own sequence)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    if angles.ndim == 2:
        angles = angles[None]  # shared positions -> broadcast batch dim
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.stack([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class CausalSelfAttention(nn.Module):
    cfg: LMConfig

    @nn.compact
    def __call__(self, x, positions, *, train: bool, kv_cache=None,
                 cache_length=None, decode: bool = False):
        from tpuframe.ops import attention as attn_ops
        from tpuframe.ops import ring_store, seq_parallel

        c = self.cfg
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (c.num_heads, c.head_dim), use_bias=False, dtype=c.jnp_dtype,
            name=name)
        q = rope(dense("query")(x), positions, c.rope_theta)
        k = rope(dense("key")(x), positions, c.rope_theta)
        v = dense("value")(x)

        if kv_cache is not None:
            # Serving path (tpuframe.serve): the cache stores post-RoPE
            # keys, so a wrapped ring slot keeps its original absolute
            # position and wraparound degrades to sliding-window
            # attention rather than silent position corruption.
            # A ring is [slots, heads, head_dim, capacity]: one token is
            # a column (serve/kv_cache.py).
            k_cache, v_cache = kv_cache
            cap = k_cache.shape[-1]
            if decode:
                # Ring write: every slot's new column at its own index
                # (modulo capacity), K's and V's each in one pass over
                # the slots (ops.ring_store), then query-length-1
                # attention over the valid prefix.
                idx = (cache_length % cap).astype(jnp.int32)
                k_cache = ring_store.ring_store(
                    k_cache, k[:, 0].astype(k_cache.dtype), idx)
                v_cache = ring_store.ring_store(
                    v_cache, v[:, 0].astype(v_cache.dtype), idx)
                valid = jnp.minimum(cache_length + 1, cap)
                y = attn_ops.decode_attention(q, k_cache, v_cache,
                                              lengths=valid)
            else:
                # Prefill: identical math to the training forward
                # (causal attention over the left-aligned prompt) plus
                # the cache write, the prompt's K/V transposed into
                # columns [0:S] — golden-logits parity with the training
                # path is by construction, not by test luck (the test
                # still checks it).
                s = x.shape[1]
                if s > cap:
                    raise ValueError(f"prompt bucket {s} exceeds "
                                     f"KV-cache capacity {cap}")

                def columns(t):   # [B, S, N, D] -> [B, N, D, S]
                    return t.transpose(0, 2, 3, 1)

                k_cache = lax.dynamic_update_slice(
                    k_cache, columns(k).astype(k_cache.dtype), (0, 0, 0, 0))
                v_cache = lax.dynamic_update_slice(
                    v_cache, columns(v).astype(v_cache.dtype), (0, 0, 0, 0))
                y = attn_ops.multihead_attention(q, k, v, causal=True,
                                                 impl=c.attn_impl)
            out = nn.DenseGeneral(c.hidden_size, axis=(-2, -1),
                                  use_bias=False, dtype=c.jnp_dtype,
                                  name="out")(y)
            return out, (k_cache, v_cache)

        mode = c.seq_mode
        if mode != "none" and not _seq_axis_bound(c.seq_axis):
            mode = "none"  # unmapped run of a seq-parallel config
        if mode == "ring":
            y = seq_parallel.ring_attention(q, k, v, axis=c.seq_axis,
                                            causal=True, impl=c.attn_impl)
        elif mode == "ulysses":
            y = seq_parallel.ulysses_attention(q, k, v, axis=c.seq_axis,
                                               causal=True, impl=c.attn_impl)
        elif mode == "none":
            y = attn_ops.multihead_attention(q, k, v, causal=True,
                                             impl=c.attn_impl)
        else:
            raise ValueError(f"unknown seq_mode {c.seq_mode!r}")
        return nn.DenseGeneral(c.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=c.jnp_dtype, name="out")(y)


class MoEMLP(nn.Module):
    """Top-k routed expert FFN (tpuframe.ops.moe). Dropped-token residual
    semantics: overflow tokens pass through with zero MLP contribution."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        from tpuframe.ops import moe as moe_ops

        c = self.cfg
        b, s, h = x.shape
        e, inter = c.moe_experts, c.intermediate_size
        tokens = x.reshape(b * s, h)
        gate_logits = nn.Dense(e, use_bias=False, name="router")(
            tokens.astype(jnp.float32))
        cap = moe_ops.capacity_for(b * s, e, c.moe_k, c.moe_capacity_factor)
        dispatch, combine, aux = moe_ops.route_topk(gate_logits, k=c.moe_k,
                                                    capacity=cap)
        self.sow("aux_loss", "load_balance", aux)

        up = self.param("up_experts", nn.initializers.lecun_normal(),
                        (e, h, inter))
        down = self.param("down_experts", nn.initializers.lecun_normal(),
                          (e, inter, h))
        dtype = c.jnp_dtype
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(dtype),
                               tokens.astype(dtype))
        hmid = nn.gelu(jnp.einsum("ech,ehi->eci", expert_in,
                                  up.astype(dtype)))
        expert_out = jnp.einsum("eci,eih->ech", hmid, down.astype(dtype))
        y = jnp.einsum("tec,ech->th", combine.astype(dtype), expert_out)
        return y.reshape(b, s, h)


class Block(nn.Module):
    cfg: LMConfig
    train: bool = False  # attribute (not call arg) so nn.remat sees only arrays
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, positions, *, kv_cache=None, cache_length=None,
                 decode: bool = False):
        c = self.cfg
        train = self.train
        h = nn.LayerNorm(use_bias=False, name="attn_ln")(x)
        new_cache = None
        if kv_cache is not None:
            h, new_cache = CausalSelfAttention(c, name="attn")(
                h, positions, train=train, kv_cache=kv_cache,
                cache_length=cache_length, decode=decode)
        else:
            h = CausalSelfAttention(c, name="attn")(h, positions,
                                                    train=train)
        h = nn.Dropout(c.dropout, deterministic=not train)(h)
        x = x + h
        h = nn.LayerNorm(use_bias=False, name="mlp_ln")(x)
        if self.use_moe:
            h = MoEMLP(c, name="moe")(h)
        else:
            h = nn.Dense(c.intermediate_size, use_bias=False,
                         dtype=c.jnp_dtype, name="up")(h)
            h = nn.gelu(h)
            h = nn.Dense(c.hidden_size, use_bias=False, dtype=c.jnp_dtype,
                         name="down")(h)
        h = nn.Dropout(c.dropout, deterministic=not train)(h)
        x = x + h
        if kv_cache is not None:
            return x, new_cache
        return x


class ScanBlockLM(nn.Module):
    """TransformerLM variant with the block stack as ONE ``nn.scan`` — the
    layer-stacked parameterization pipeline parallelism shards.

    Params: ``blocks`` holds every Block's weights stacked on a leading
    layer dim ``[L, ...]`` (also O(1) compile time in depth — the scan-over-
    layers idiom).  Three apply modes through the one compact method:

      * default: full forward — embed → scan(L blocks) → final_ln → head;
      * ``stage=True``: ONLY the block stack, with however many layers the
        passed ``blocks`` param slice carries (shard_map slices the leading
        dim over ``pipe``, so each stage runs its own L/S contiguous
        layers) — the ``stage_fn`` for tpuframe.parallel.pp.pipeline_apply;
      * ``embed_only=True`` / ``head_only=True``: the replicated ends,
        computed on every stage (cheap vs the blocks; keeps the SPMD
        program identical everywhere).

    MoE and sequence-parallel attention are not composed with this variant
    (``seq_mode="none"``, ``moe_experts=0`` enforced); use TransformerLM
    for those.
    """

    cfg: LMConfig = field(default_factory=LMConfig)

    @nn.compact
    def __call__(self, inputs, *, train: bool = False, stage: bool = False,
                 stage_layers: int | None = None,
                 embed_only: bool = False, head_only: bool = False,
                 hidden_only: bool = False):
        c = self.cfg
        if c.seq_mode != "none" or c.moe_experts > 0:
            raise ValueError("ScanBlockLM composes with pipeline parallelism"
                             " only; seq_mode must be 'none' and moe off")

        def block_stack(x, n_layers):
            positions = jnp.arange(x.shape[1])
            target = mem.remat_module(_ScanBlock) if c.remat \
                else _ScanBlock
            Scanned = nn.scan(
                target,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=n_layers,
            )
            (x, _), _ = Scanned(c, train, name="blocks")((x, positions), None)
            return x

        if stage:
            # inputs: hidden states [B, S, H]; the caller says how many of
            # the stacked layers its ``blocks`` param slice carries.
            if stage_layers is None:
                raise ValueError("stage=True requires stage_layers")
            return block_stack(inputs, stage_layers)
        if head_only:
            x = nn.LayerNorm(use_bias=False, name="final_ln")(inputs)
            if hidden_only:
                # normed hidden states for the chunked fused loss
                # (tpuframe.ops.fused_xent) — lm_head applied there.
                return x
            logits = nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
            return logits.astype(jnp.float32)

        x = nn.Embed(c.vocab_size, c.hidden_size, name="embed")(inputs)
        x = x.astype(c.jnp_dtype)
        if embed_only:
            return x
        x = block_stack(x, c.num_layers)
        x = nn.LayerNorm(use_bias=False, name="final_ln")(x)
        if hidden_only:
            # honor standalone hidden_only like TransformerLM does — the
            # harness's fused-xent loss path calls it without head_only
            # (transformer-lm-pp run on a non-pp mesh).
            return x
        logits = nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        return logits.astype(jnp.float32)


class _ScanBlock(nn.Module):
    """``Block`` wrapped for ``nn.scan``: carry = (hidden, positions).
    Delegates to the one Block implementation so the dense architecture
    cannot drift between the looped and the scanned/pipelined variants."""

    cfg: LMConfig
    train: bool = False

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        y = Block(self.cfg, self.train, name="block")(x, positions)
        y = mem.seam(y, "block_out")
        return (y, positions), None


class TransformerLM(nn.Module):
    """input_ids [B, S_local] → logits [B, S_local, V] (f32)."""

    cfg: LMConfig = field(default_factory=LMConfig)

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 hidden_only: bool = False, kv_cache=None,
                 cache_length=None, decode: bool = False):
        """``hidden_only=True`` returns the post-final-LayerNorm hidden
        states ``[B, S, H]`` instead of logits — the input the chunked
        fused cross-entropy (tpuframe.ops.fused_xent) consumes together
        with the ``lm_head`` kernel, so the ``[B, S, V]`` logits never
        materialize in HBM.  init() must run with the default full path so
        the lm_head parameters exist.

        Serving path (tpuframe.serve): ``kv_cache`` is a per-layer tuple
        of ``(k, v)`` pairs, each ``[B, N, D, capacity]``; ``cache_length``
        ``[B]`` counts tokens already cached.  ``decode=False`` prefills a
        left-aligned (padded) prompt — same math as the training forward —
        writing every layer's K/V; ``decode=True`` runs ONE new token per
        sequence through the query-length-1 attention entry
        (ops.attention.decode_attention) after storing its K/V column at
        its own ring index (ops.ring_store).
        Returns ``(logits, new_kv_cache)``.  Sequence parallelism and MoE
        do not compose with the cache path (serving shards over batch)."""
        c = self.cfg
        s_local = input_ids.shape[-1]
        if kv_cache is not None:
            if c.seq_mode != "none" or c.moe_experts > 0:
                raise ValueError("the KV-cache path serves dense batch-"
                                 "parallel configs only; seq_mode must be"
                                 " 'none' and moe off")
            if len(kv_cache) != c.num_layers:
                raise ValueError(f"kv_cache has {len(kv_cache)} layers; "
                                 f"model has {c.num_layers}")
            if decode:
                if s_local != 1:
                    raise ValueError(f"decode wants one token per "
                                     f"sequence, got S={s_local}")
                positions = cache_length[:, None]  # [B, 1] absolute
            else:
                positions = jnp.arange(s_local)
            x = nn.Embed(c.vocab_size, c.hidden_size,
                         name="embed")(input_ids)
            x = x.astype(c.jnp_dtype)
            new_caches = []
            for i in range(c.num_layers):
                x, layer_cache = Block(c, False, False,
                                       name=f"block_{i}")(
                    x, positions, kv_cache=kv_cache[i],
                    cache_length=cache_length, decode=decode)
                new_caches.append(layer_cache)
            x = nn.LayerNorm(use_bias=False, name="final_ln")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False,
                              name="lm_head")(x)
            return logits.astype(jnp.float32), tuple(new_caches)
        # Global positions: offset by this device's chunk index when the
        # sequence dimension is sharded over the seq axis.
        start = 0
        if c.seq_mode != "none" and _seq_axis_bound(c.seq_axis):
            start = lax.axis_index(c.seq_axis) * s_local
        positions = start + jnp.arange(s_local)

        x = nn.Embed(c.vocab_size, c.hidden_size, name="embed")(input_ids)
        x = x.astype(c.jnp_dtype)
        # Named checkpoint seams: identity unless a per_block/save_named
        # remat policy (tpuframe.mem) elects to save exactly these.
        x = mem.seam(x, "embed_out")
        block = mem.remat_module(Block) if c.remat else Block
        for i in range(c.num_layers):
            use_moe = c.moe_experts > 0 and (i + 1) % c.moe_every == 0
            x = block(c, train, use_moe, name=f"block_{i}")(x, positions)
            x = mem.seam(x, "block_out")
        x = nn.LayerNorm(use_bias=False, name="final_ln")(x)
        if hidden_only:
            return x
        logits = nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        return logits.astype(jnp.float32)
