"""Multi-head attention core with pluggable kernels.

The reference gets attention from HF transformers' torch BERT (cuDNN kernels
under the hood).  Here the op is a dispatch point:
  - ``xla``: einsum formulation — XLA fuses softmax into the matmuls well on
    TPU for BERT-scale sequence lengths (128–512, [B:10]).
  - ``pallas``: a flash-attention TPU kernel (tpuframe.ops.flash_attention),
    block-tiled for MXU/VMEM — the long-sequence path.

Selection: explicit ``impl=`` argument, else the ``TPUFRAME_ATTN_IMPL`` env
var, else ``xla``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def multihead_attention(
    q: jax.Array,  # [B, S, N, D]
    k: jax.Array,  # [B, S, N_kv, D]: N_kv divides N (grouped-query heads)
    v: jax.Array,  # [B, S, N_kv, D_v]: D_v may differ from D
    *,
    # a second score term (latent attention): (q_rope [B, S, N, D_r],
    # k_rope [B, S, N_r, D_r]), N_r dividing N; scores are
    # (q.k + q_rope.k_rope) / sqrt(D + D_r)
    rope: tuple[jax.Array, jax.Array] | None = None,
    mask: jax.Array | None = None,  # [B, S] 1=keep or broadcastable [B,1,S,S]
    causal: bool = False,
    window: int | None = None,  # with causal: key j attends iff i - j < window
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    impl: str | None = None,
) -> jax.Array:
    """Query head ``h`` reads K/V head ``h // (N / N_kv)``, and ``rope``'s
    key head ``h // (N / N_r)``.  The flash kernels read a shared head
    where it lies; the XLA composition repeats it."""
    impl = impl or os.environ.get("TPUFRAME_ATTN_IMPL", "xla")
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")
    if impl == "pallas":
        from tpuframe.ops import flash_attention, kernel_impl

        op = "flash_attention" if rope is None else "flash_mla_attention"
        if dropout_rate != 0.0:
            why = "dropout"
        elif rope is not None:
            if mask is not None or window is not None:
                why = "a key mask or a window beside a second score term"
            elif not flash_attention.mla_supported(q, rope[0], k, rope[1], v):
                why = (f"shapes q={q.shape} + {rope[0].shape} k={k.shape} + "
                       f"{rope[1].shape} v={v.shape} do not tile")
            else:
                return flash_attention.flash_mla(q, rope[0], k, rope[1], v,
                                                 causal=causal)
        elif v.shape[-1] != q.shape[-1]:
            why = f"v is {v.shape[-1]} wide, q {q.shape[-1]}"
        elif not flash_attention.supported(q, k):
            why = f"shapes q={q.shape} k={k.shape} do not tile"
        elif mask is not None and mask.ndim != 2:
            why = "mask is not a [B, S] key mask"
        else:
            return flash_attention.flash_mha(q, k, v, mask=mask, causal=causal,
                                             window=window)
        # The XLA composition stands in; said once, never silently.
        kernel_impl.record(op, "xla", why)
        impl = "xla"
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}")
    if rope is not None:    # one score product over the concatenated widths
        q_rope, k_rope = rope
        k_rope = jnp.repeat(k_rope, k.shape[2] // k_rope.shape[2], axis=2)
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([k, k_rope], axis=-1)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if causal:
        s_q, s_kv = q.shape[1], k.shape[1]
        tri = jnp.tril(jnp.ones((s_q, s_kv), bool))
        if window is not None:
            tri = jnp.logical_and(tri, jnp.triu(tri, 1 - window))
        tri = tri[None, None]
        if mask is not None:
            pad = mask[:, None, None, :] if mask.ndim == 2 else mask
            tri = jnp.logical_and(tri, pad.astype(bool))
        mask = tri
    return _xla_attention(q, k, v, mask=mask, dropout_rate=dropout_rate,
                          dropout_rng=dropout_rng)


def decode_attention(
    q: jax.Array,        # [B, 1, N, D] — the query-length-1 decode entry
    k_cache: jax.Array,  # [B, N, D, S_kv] — the ring of post-RoPE keys
    v_cache: jax.Array,  # [B, N, D, S_kv]
    *,
    lengths: jax.Array,  # [B] int32 — valid cache entries per sequence
) -> jax.Array:
    """Decode-mode attention: one new query token against the KV ring.

    The serving counterpart of :func:`multihead_attention`
    (tpuframe.serve), on the ring's own layout (serve/kv_cache.py): a
    cached token is a column, the capacity axis is minor.  Causality is a
    *length mask*, not a triangle: the ring holds exactly the tokens the
    new position may attend, padded to its bucketed capacity, so the mask
    is ``arange(S_kv) < lengths`` per sequence.  Where the rings tile and
    Mosaic is at hand this is the kernel of ``ops.decode_attention``,
    which reads only the lane blocks below each sequence's ``lengths``;
    elsewhere the einsum formulation stands in — two loop fusions that
    each read one whole ring, whatever it holds — and the run's
    ``kernel_impl`` record says which ran.  Same math as
    :func:`_xla_attention` with a key mask.
    """
    from tpuframe.ops import decode_attention as kernel, kernel_impl

    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"decode_attention wants q [B, 1, N, D]; "
                         f"got {q.shape}")
    b, _, n, d = q.shape
    s_kv = k_cache.shape[-1]
    if k_cache.shape != (b, n, d, s_kv) or v_cache.shape != k_cache.shape:
        raise ValueError(f"decode_attention wants rings [B, N, D, S_kv] = "
                         f"[{b}, {n}, {d}, S_kv]; got {k_cache.shape}, "
                         f"{v_cache.shape}")
    if not kernel.supported(q, k_cache) or v_cache.dtype != k_cache.dtype:
        why = (f"q {q.shape} {q.dtype} rings {k_cache.shape} "
               f"{k_cache.dtype} do not tile")
    else:
        why = kernel_impl.no_mosaic()
    if why is not None:
        kernel_impl.record("decode_attention", "xla", why)
        return _xla_decode_attention(q, k_cache, v_cache, lengths)
    interpret = kernel_impl.resolve_interpret(
        "decode_attention", None, kernel.describe(k_cache))
    return kernel.decode_attention(q, k_cache, v_cache, lengths,
                                   interpret=interpret)


def _xla_decode_attention(q, k_cache, v_cache, lengths):
    """The composition the kernel replaces: every column of every ring."""
    d, s_kv = q.shape[-1], k_cache.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.einsum("bqnd,bndk->bnqk", q * scale, k_cache,
                        preferred_element_type=jnp.float32)
    mask = jnp.arange(s_kv)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bnqk,bndk->bqnd", probs, v_cache)


def _xla_attention(q, k, v, *, mask, dropout_rate, dropout_rng):
    depth = q.shape[-1]
    scale = 1.0 / jnp.sqrt(depth).astype(q.dtype)
    # [B, N, S, S] scores; accumulate in f32 for softmax stability.
    scores = jnp.einsum("bqnd,bknd->bnqk", q * scale, k,
                        preferred_element_type=jnp.float32)
    if mask is not None:
        if mask.ndim == 2:  # [B, S] key padding mask
            mask = mask[:, None, None, :]
        scores = jnp.where(mask.astype(bool), scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)
