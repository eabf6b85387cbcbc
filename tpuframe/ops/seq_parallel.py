"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference never scales the sequence dimension (its longest workload is
BERT-base GLUE, seq ≤ 512 — SURVEY.md §5.7); this framework makes
long-context training first-class.  Both strategies run *inside* a
``shard_map`` over the mesh's ``seq`` axis, with the sequence dimension of
activations sharded across chips:

  * **Ring attention** — K/V chunks rotate around the ``seq`` axis ring via
    ``lax.ppermute`` (ICI neighbor hops); each device accumulates its query
    chunk's attention over every K/V chunk with online-softmax merging, so
    the full S×S score matrix never exists on any chip and per-chip memory
    is O(S/n).  This is the classic blockwise/ring formulation; gradients
    flow through the rotation automatically (the transpose of ppermute is
    the reverse ring).

  * **Ulysses** — two ``all_to_all``s re-shard [B, S/n, N, D] → [B, S, N/n, D]
    so each device sees the whole sequence for a subset of heads, runs plain
    (or pallas flash) attention locally, then re-shards back.  Cheaper in
    collective volume for moderate S; requires heads % seq_size == 0.

Both are numerically identical to full attention over the gathered sequence
(tests/test_seq_parallel.py asserts this against the XLA reference on the
8-device virtual mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30  # matches tpuframe.ops.flash_attention.NEG_INF


def _chunk_attn_whole(q, k, v, keep, scale):
    """Unnormalized blockwise attention in f32 (scores fully materialized).

    q: [B, Cq, N, D]; k/v: [B, Ck, N, D]; keep: [B, 1, Cq, Ck] bool or None.
    Returns (acc [B, Cq, N, D] f32, m [B, N, Cq] f32, l [B, N, Cq] f32).
    """
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B, N, Cq]
    p = jnp.exp(s - m[..., None])
    if keep is not None:
        p = jnp.where(keep, p, 0.0)  # fully-masked rows stay exactly zero
    l = jnp.sum(p, axis=-1)                                   # [B, N, Cq]
    acc = jnp.einsum("bnqk,bknd->bqnd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _chunk_attn(q, k, v, keep, scale, q_chunk=None):
    """``_chunk_attn_whole`` with a bounded score footprint.

    The whole-chunk scores are [B, N, Cq, Ck] f32 — at 32k over 4 devices
    that is 12 x 8192^2 x 4 B = 3.2 GB per ring stage, which OOMs the chip
    (found by the offline v5e AOT compile, PERF.md §9).  ``q_chunk`` caps
    the live score block at [B, N, q_chunk, Ck] by lax.map-ing over query
    sub-chunks: rows are independent given a fixed K/V chunk, so results
    concatenate exactly — no extra merging, bit-identical math.
    """
    b, cq, nh, d = q.shape
    if q_chunk is None or cq <= q_chunk:
        return _chunk_attn_whole(q, k, v, keep, scale)
    n_sub, tail = divmod(cq, q_chunk)
    head = n_sub * q_chunk
    # jax.checkpoint: without it, lax.map's transpose STACKS each
    # sub-chunk's softmax residuals ([n_sub, B, N, q_chunk, Ck] f32 — and
    # the enclosing ring scan stacks that again per stage), which is the
    # multi-GB saved-buffer class the chunking exists to eliminate.  With
    # it, the backward recomputes one sub-chunk's scores at a time.
    core = jax.checkpoint(
        lambda qi, kp: _chunk_attn_whole(qi, k, v, kp, scale))
    qs = q[:, :head].reshape(b, n_sub, q_chunk, nh, d).transpose(
        1, 0, 2, 3, 4)
    if keep is not None:
        ck = keep.shape[-1]
        ks = keep[:, :, :head].reshape(
            b, 1, n_sub, q_chunk, ck).transpose(2, 0, 1, 3, 4)
        acc, m, l = lax.map(lambda xs: core(xs[0], xs[1]), (qs, ks))
    else:
        acc, m, l = lax.map(lambda qi: core(qi, None), qs)
    acc = acc.transpose(1, 0, 2, 3, 4).reshape(b, head, nh, d)
    m = m.transpose(1, 2, 0, 3).reshape(b, nh, head)
    l = l.transpose(1, 2, 0, 3).reshape(b, nh, head)
    if tail:
        # Ragged remainder: rows are independent, so one extra sub-chunk
        # keeps the result exact without re-admitting whole-chunk scores.
        acc_t, m_t, l_t = core(
            q[:, head:], None if keep is None else keep[:, :, head:])
        acc = jnp.concatenate([acc, acc_t], axis=1)
        m = jnp.concatenate([m, m_t], axis=-1)
        l = jnp.concatenate([l, l_t], axis=-1)
    return acc, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis: str = "seq",
                   mask: jax.Array | None = None,
                   causal: bool = False,
                   q_chunk: int | None = 1024,
                   impl: str | None = None) -> jax.Array:
    """Exact attention over a sequence sharded across the ``axis`` ring.

    Must be called inside ``shard_map`` with ``axis`` bound.  Per-device
    inputs are the local sequence chunk ``[B, S/n, N, D]`` (and ``mask``
    ``[B, S/n]``, 1 = attend, for the *local keys*).  Output is the local
    query chunk's attention over the FULL sequence, ``[B, S/n, N, D]``.

    Causal masking uses global positions: device ``i``'s queries occupy
    ``[i*C, (i+1)*C)`` of the gathered sequence.

    ``q_chunk`` bounds the per-stage score materialization (see
    ``_chunk_attn``); identical results, identical wire traffic — only
    the live f32 score block shrinks.  None disables.

    ``impl`` selects the per-stage attention kernel — explicit argument,
    else ``TPUFRAME_ATTN_IMPL``, else ``xla``:

      * ``"xla"`` — the chunked einsum stages below (always available).
      * ``"pallas"`` — each stage is the flash kernel
        (:func:`tpuframe.ops.flash_attention.flash_mha_lse`); stages
        merge via logsumexp weights instead of raw (m, l).  The
        capacity audit (PERF.md §9) found the XLA stages lower-bound
        ring at ≥2x Ulysses+flash bytes at 32k — and ring is the
        documented FALLBACK exactly when heads don't divide the sp
        degree, so the fallback path gets the kernel too.  Causal
        masking is a stage-level trichotomy (owner below / on / above
        the diagonal), so above-diagonal stages skip all compute and
        the diagonal stage reuses the kernel's own block-skipping tri
        mask.  Unsupported shapes fall back to ``xla`` (same contract
        as tpuframe.ops.attention).
    """
    import os

    impl = impl or os.environ.get("TPUFRAME_ATTN_IMPL", "xla")
    if impl == "pallas":
        from tpuframe.ops import flash_attention as fa
        from tpuframe.ops import kernel_impl

        # Interpreter guard: the pallas HLO interpreter's internal
        # slicing trips shard_map's vma check (see the CPU tests'
        # check_vma=False concession), so a config that requests pallas
        # ring stages quietly keeps the numerically-identical XLA stages
        # when the kernel would interpret (CPU harness runs, dryrun) —
        # real-TPU and offline-AOT contexts lower Mosaic and take the
        # flash path.  TPUFRAME_RING_FLASH_INTERPRET=1 forces the flash
        # stages under the interpreter (the kernel tests do, with
        # check_vma=False shard_maps).
        interpreting, why = kernel_impl.interpret_default()
        forced = os.environ.get("TPUFRAME_RING_FLASH_INTERPRET") == "1"
        if fa.supported(q, k) and (mask is None or mask.ndim == 2) \
                and (not interpreting or forced):
            return _ring_flash(q, k, v, axis=axis, mask=mask, causal=causal)
        kernel_impl.record(
            "ring_attention", "xla",
            f"flash stages would interpret ({why})" if interpreting
            else f"shapes q={q.shape} k={k.shape} / mask do not fit the "
                 f"flash stages")
        impl = "xla"
    elif impl != "xla":
        raise ValueError(f"unknown ring attention impl {impl!r}")

    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    b, c, heads, d = q.shape
    scale = d ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]  # rotate kv chunks rightward

    def make_keep(kv_owner, kv_mask):
        keep = None
        if kv_mask is not None:
            keep = (kv_mask != 0)[:, None, None, :]           # [B,1,1,Ck]
            keep = jnp.broadcast_to(keep, (b, 1, c, c))
        if causal:
            q_pos = my * c + jnp.arange(c)[:, None]           # [Cq, 1]
            kv_pos = kv_owner * c + jnp.arange(c)[None, :]    # [1, Ck]
            tri = (q_pos >= kv_pos)[None, None]               # [1,1,Cq,Ck]
            tri = jnp.broadcast_to(tri, (b, 1, c, c))
            keep = tri if keep is None else jnp.logical_and(keep, tri)
        return keep

    def step(carry, i):
        acc, m, l, kv_k, kv_v, kv_mask = carry
        kv_owner = (my - i) % n  # whose chunk we hold after i rotations
        # checkpoint: the ring scan's transpose must save only the small
        # per-stage inputs (kv chunk, [B,Ck] mask, scalar owner), not the
        # stage's score-sized softmax residuals stacked n times — the keep
        # mask ([B,1,Cq,Ck]) is built INSIDE so it is recomputed too.
        def stage(qq, kk, vv, owner, kmask):
            return _chunk_attn(qq, kk, vv, make_keep(owner, kmask), scale,
                               q_chunk=q_chunk)

        acc_c, m_c, l_c = jax.checkpoint(stage)(q, kv_k, kv_v, kv_owner,
                                                kv_mask)
        m_new = jnp.maximum(m, m_c)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(m_c - m_new)
        # [B, N, Cq] stats scale the [B, Cq, N, D] accumulator.
        t = lambda x: x.transpose(0, 2, 1)[..., None]  # noqa: E731
        acc = acc * t(a1) + acc_c * t(a2)
        l = l * a1 + l_c * a2
        m = m_new
        kv_k = lax.ppermute(kv_k, axis, perm)
        kv_v = lax.ppermute(kv_v, axis, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis, perm)
        return (acc, m, l, kv_k, kv_v, kv_mask), None

    # Fresh accumulators are unvarying; mark them varying over the same mesh
    # axes as q so the scan carry type is stable under shard_map's vma checks.
    vary = lambda x: lax.pcast(  # noqa: E731
        x, tuple(jax.typeof(q).vma), to="varying")
    init = (
        vary(jnp.zeros((b, c, heads, d), jnp.float32)),
        vary(jnp.full((b, heads, c), NEG_INF, jnp.float32)),
        vary(jnp.zeros((b, heads, c), jnp.float32)),
        k, v, mask,
    )
    (acc, m, l, *_), _ = lax.scan(step, init, jnp.arange(n))
    l = l.transpose(0, 2, 1)[..., None]                       # [B, Cq, N, 1]
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


def _ring_flash(q, k, v, *, axis, mask, causal):
    """Ring attention with flash-kernel stages (see ring_attention docs).

    Each stage returns the kernel's normalized output plus its logsumexp
    rows; stages merge exactly via

        LSE' = logaddexp(LSE, lse_i)
        out' = out·exp(LSE - LSE') + out_i·exp(lse_i - LSE')

    which equals the (acc, m, l) online-softmax merge of the XLA path.
    Both merge factors carry gradient: flash_mha_lse's backward folds the
    lse cotangent into its delta rows, so XLA autodiff of this merge +
    the per-stage custom_vjp is the exact ring backward.  Stages sit
    under jax.checkpoint like the XLA path — the scan saves only rotated
    kv chunks, never per-stage kernel residuals.
    """
    from tpuframe.ops import flash_attention as fa

    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    b, c, heads, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    vary = lambda x: lax.pcast(  # noqa: E731
        x, tuple(jax.typeof(q).vma), to="varying")

    def stage(qq, kk, vv, owner, kmask):
        def run(causal_flag):
            def f(_):
                return fa.flash_mha_lse(qq, kk, vv, mask=kmask,
                                        causal=causal_flag)
            return f

        if not causal:
            return run(False)(None)

        def above(_):
            # Strictly above the diagonal: nothing attends — no kernel
            # launch, zero contribution, zero gradient to this kv chunk.
            return (vary(jnp.zeros((b, c, heads, d), qq.dtype)),
                    vary(jnp.full((b, heads, c), NEG_INF, jnp.float32)))

        idx = jnp.where(owner < my, 0, jnp.where(owner == my, 1, 2))
        return lax.switch(idx, [run(False), run(True), above], None)

    def step(carry, i):
        out_acc, lse_acc, kv_k, kv_v, kv_mask = carry
        owner = (my - i) % n
        o_i, lse_i = jax.checkpoint(stage)(q, kv_k, kv_v, owner, kv_mask)
        lse_new = jnp.logaddexp(lse_acc, lse_i)            # [B, N, C]
        w1 = jnp.exp(lse_acc - lse_new)
        w2 = jnp.exp(lse_i - lse_new)
        t = lambda x: x.transpose(0, 2, 1)[..., None]  # noqa: E731
        out_acc = out_acc * t(w1) + o_i.astype(jnp.float32) * t(w2)
        kv_k = lax.ppermute(kv_k, axis, perm)
        kv_v = lax.ppermute(kv_v, axis, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis, perm)
        return (out_acc, lse_new, kv_k, kv_v, kv_mask), None

    init = (
        vary(jnp.zeros((b, c, heads, d), jnp.float32)),
        vary(jnp.full((b, heads, c), NEG_INF, jnp.float32)),
        k, v, mask,
    )
    (out, _lse, *_), _ = lax.scan(step, init, jnp.arange(n))
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis: str = "seq",
                      mask: jax.Array | None = None,
                      causal: bool = False,
                      impl: str | None = None) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    Re-shards seq→heads so each device runs full-sequence attention on
    ``heads/n`` heads — the inner attention is the regular dispatch
    (``tpuframe.ops.attention``), so the pallas flash kernel applies.
    Requires ``heads % axis_size == 0``.
    """
    from tpuframe.ops import attention as attn_ops

    n = lax.axis_size(axis)
    b, c, heads, d = q.shape
    if heads % n != 0:
        raise ValueError(f"ulysses needs heads ({heads}) % seq axis ({n}) == 0")

    def to_heads(x):  # [B, S/n, N, D] → [B, S, N/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):    # [B, S, N/n, D] → [B, S/n, N, D]
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    full_mask = None
    if mask is not None:
        full_mask = lax.all_gather(mask, axis, axis=1, tiled=True)  # [B, S]
    out = attn_ops.multihead_attention(qh, kh, vh, mask=full_mask,
                                        causal=causal, impl=impl)
    return to_seq(out)
