"""Flash attention as a Pallas TPU kernel — the framework's hot-op path.

The reference's attention ran inside HF torch BERT on cuDNN (SURVEY.md §3a
"Model defs"); its FLOPs lived in fused CUDA kernels.  The TPU-native
equivalent is a block-tiled online-softmax attention kernel that keeps the
S×S score matrix out of HBM entirely:

  * forward: for each query block, stream key/value blocks through VMEM,
    maintaining running max ``m``, normalizer ``l`` and an f32 accumulator —
    one HBM pass over K/V, scores never materialized.
  * backward: two kernels (dq-major and dkv-major), recomputing probabilities
    from the saved logsumexp instead of storing them — the standard
    flash-attention-2 residual scheme (O, logsumexp, delta=rowsum(dO·O)).

Block sizes default to 128 — the MXU tile edge — so every matmul in the loop
is a full systolic-array issue.  Accumulation is float32 regardless of input
dtype (bf16 inputs keep bf16 in HBM, f32 in VMEM).

Used through :func:`tpuframe.ops.attention.multihead_attention` with
``impl="pallas"`` (or ``TPUFRAME_ATTN_IMPL=pallas``); CPU tests run the same
kernel under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 128 = the MXU tile edge.  Resolution order (tpuframe.tune):
# TPUFRAME_FA_BLOCK_Q/K env > tuning-DB measured > tuning-DB predicted >
# 128 — and the DB tiers only engage when TPUFRAME_TUNE_GEN names the
# target generation, so plain runs and the fast test tier see 128/128.
from tpuframe.ops import kernel_impl
from tpuframe.tune import db as _tune_db  # stdlib-only module
from tpuframe.tune import roofline as _roofline

DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K = _tune_db.resolve_fa_blocks(128, 128)
NEG_INF = -1e30  # softmax mask fill; finite so (x - x) stays 0, not nan

_LANES = 128  # VMEM lane width: per-row stats are stored lane-broadcast


def _lse_lane_major() -> bool:
    """Generation-conditional lse/delta layout (PERF.md §12.2).

    The per-row residuals (logsumexp, delta) are logically [rows] vectors;
    as kernel operands they need a 2-D in-block shape.  Sublane-major
    ([bq, 1]) matches the running stats' natural orientation but pads the
    HBM array's trailing dim 1 → 128 lanes — a 128x residual blow-up that
    pushed lm_long's dp1×sp8 capacity-edge mesh back over v5e's HBM.
    Lane-major ([1, bq]) pads 1 → 8 sublanes instead (16x less), but the
    in-kernel [bq, 1] ↔ [1, bq] re-layout lowers through tpu.dynamic_gather
    — "Sublane gather not supported by this TPU generation" on v4 (the
    offline v4 audit, PERF.md §12.1).  So: lane-major for every generation
    newer than v4, sublane-major for v4.  The generation is the attached
    device's (or TPUFRAME_TUNE_GEN, for a compile that targets a described
    chip); with no TPU attached and none named it is only assumed, and the
    run keeps the layout every generation can compile."""
    gen, source = _roofline.device_generation()
    return source != "assumed" and gen != "v4"


def _causal_dispatch(causal, qi, kv, block_q, block_k, compute):
    """Run ``compute(need_tri)`` for this block's causal region.

    Three regions by block position: strictly ABOVE the diagonal
    contributes nothing (skip entirely); STRADDLING it needs the
    per-element tri mask; strictly BELOW needs no tri at all — for long
    sequences most blocks are below, so skipping the iota/compare/select
    chain there removes real VPU work.  Non-causal: one unmasked call.
    """
    if not causal:
        compute(False)
        return
    first_row, last_row = qi * block_q, qi * block_q + (block_q - 1)
    first_col, last_col = kv * block_k, kv * block_k + (block_k - 1)

    @pl.when(first_row >= last_col)
    def _below():
        compute(False)

    @pl.when(jnp.logical_and(last_row >= first_col, first_row < last_col))
    def _straddle():
        compute(True)


def _sds(like: jax.Array, shape, dtype) -> jax.ShapeDtypeStruct:
    """out_shape that inherits ``like``'s varying-mesh-axes, so the kernel
    works unchanged inside ``shard_map`` (where jax requires outputs to
    declare their vma) and outside it (empty vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def supported(q: jax.Array, k: jax.Array | None = None,
              block_q: int = DEFAULT_BLOCK_Q,
              block_k: int = DEFAULT_BLOCK_K) -> bool:
    """True when shapes fit the kernel's static tiling (else caller falls
    back to the XLA einsum path, tpuframe.ops.attention)."""
    if q.ndim != 4:
        return False
    _, s_q, _, d = q.shape
    s_kv = s_q if k is None else k.shape[1]
    bq, bk = min(block_q, s_q), min(block_k, s_kv)
    # seq dims must tile into whole blocks and stay sublane-aligned (mult of
    # 8); head dim beyond 256 would blow the per-block VMEM budget.
    return (d <= 256 and s_q % bq == 0 and s_kv % bk == 0
            and s_q % 8 == 0 and s_kv % 8 == 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                 # outputs
                acc_ref, m_ref, l_ref,          # scratch
                *, scale: float, causal: bool, block_q: int, block_k: int,
                n_kv: int, lane_lse: bool = False, precision=None):
    qi = pl.program_id(1)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute(need_tri):
        q = q_ref[0]                     # [bq, d]
        k = k_ref[0]                     # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale   # [bq, bk]

        keep = None                                       # [bq, bk] or None
        if mask_ref is not None:
            keep = jnp.broadcast_to(mask_ref[0, 0][None, :] != 0, s.shape)
        if need_tri:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            tri = qi * block_q + rows >= kv * block_k + cols
            keep = tri if keep is None else jnp.logical_and(keep, tri)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # rescale factor
        p = jnp.exp(s - m_new)                            # [bq, bk]
        if keep is not None:
            # Explicit zeroing (not exp-underflow): a fully-masked row keeps
            # l == 0 and yields zero output + NEG_INF lse, and the backward
            # recompute below reproduces exactly p == 0 for it.
            p = jnp.where(keep, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    _causal_dispatch(causal, qi, kv, block_q, block_k, compute)

    @pl.when(kv == n_kv - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows → zeros
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # logsumexp residual for the backward pass.  Layout is generation-
        # conditional (_lse_lane_major): lane-major [1, bq] where the
        # sublane<->lane re-layout compiles (v5e+ — 16x less HBM padding on
        # the residual array), sublane-major [bq, 1] on v4/unknown, where
        # Mosaic lowers the re-layout as tpu.dynamic_gather — "Sublane
        # gather not supported by this TPU generation" (the offline v4
        # audit, PERF.md §12).
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0] = lse.reshape(1, block_q) if lane_lse else lse


def _flash_fwd(q, k, v, mask, *, scale, causal, block_q, block_k, interpret,
               precision=None):
    bn, s_q, d = q.shape
    s_kv = k.shape[1]
    bq, bk = min(block_q, s_q), min(block_k, s_kv)
    n_q, n_kv = s_q // bq, s_kv // bk
    grid = (bn, n_q, n_kv)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),          # q
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),          # k
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),          # v
    ]
    args = [q, k, v]
    lane = _lse_lane_major()
    if mask is not None:
        n_heads = bn // mask.shape[0]
        in_specs.insert(0, pl.BlockSpec(
            (1, 1, bk), lambda b, i, j, h=n_heads: (b // h, 0, j)))
        args.insert(0, mask[:, None, :])
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, n_kv=n_kv, lane_lse=lane,
            precision=precision)
    else:
        kernel = functools.partial(
            _fwd_kernel, None, scale=scale, causal=causal,
            block_q=bq, block_k=bk, n_kv=n_kv, lane_lse=lane,
            precision=precision)

    lse_spec = (pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)) if lane
                else pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)))
    lse_shape = (bn, 1, s_q) if lane else (bn, s_q, 1)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",   # the op's name in a profiler trace
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            lse_spec,
        ],
        out_shape=[
            _sds(q, (bn, s_q, d), q.dtype),
            _sds(q, lse_shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        # batch and q-block dims carry no cross-iteration state (the
        # acc/m/l scratch carry lives on the kv dim only): declaring them
        # parallel lets Mosaic schedule/pipeline them freely.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out, (lse[:, 0, :] if lane else lse[:, :, 0])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, lse_ref, mask_ref, *, scale, need_tri,
                 qi, kv, block_q, block_k, lane_lse=False, precision=None):
    """Rebuild the probability block from saved logsumexp (f32)."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale
    keep = None
    if mask_ref is not None:
        keep = jnp.broadcast_to(mask_ref[0, 0][None, :] != 0, s.shape)
    if need_tri:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        tri = qi * block_q + rows >= kv * block_k + cols
        keep = tri if keep is None else jnp.logical_and(keep, tri)
    lse = lse_ref[0]                           # [bq, 1] (or [1, bq] lane)
    if lane_lse:
        lse = lse.reshape(block_q, 1)
    p = jnp.exp(jnp.where(keep, s, NEG_INF) - lse) if keep is not None \
        else jnp.exp(s - lse)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)                         # see fwd kernel
    return p                                                # [bq, bk]


def _bwd_dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k, n_kv,
                   lane_lse=False, precision=None):
    qi = pl.program_id(1)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute(need_tri):
        p = _recompute_p(q_ref, k_ref, lse_ref, mask_ref, scale=scale,
                         need_tri=need_tri, qi=qi, kv=kv,
                         block_q=block_q, block_k=block_k,
                         lane_lse=lane_lse, precision=precision)
        dp = jax.lax.dot_general(                       # dO @ V^T  [bq, bk]
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        delta = (delta_ref[0].reshape(block_q, 1) if lane_lse
                 else delta_ref[0])
        ds = p * (dp - delta)                           # [bq, bk]
        dq_acc[...] += scale * jax.lax.dot_general(     # ds @ K    [bq, d]
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    _causal_dispatch(causal, qi, kv, block_q, block_k, compute)

    @pl.when(kv == n_kv - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, n_q,
                    lane_lse=False, precision=None):
    kv = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute(need_tri):
        p = _recompute_p(q_ref, k_ref, lse_ref, mask_ref, scale=scale,
                         need_tri=need_tri, qi=qi, kv=kv,
                         block_q=block_q, block_k=block_k,
                         lane_lse=lane_lse, precision=precision)
        dv_acc[...] += jax.lax.dot_general(             # P^T @ dO  [bk, d]
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        delta = (delta_ref[0].reshape(block_q, 1) if lane_lse
                 else delta_ref[0])
        ds = p * (dp - delta)
        dk_acc[...] += scale * jax.lax.dot_general(     # ds^T @ Q  [bk, d]
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    _causal_dispatch(causal, qi, kv, block_q, block_k, compute)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, mask, out, lse, do, *, scale, causal,
               block_q, block_k, interpret, precision=None, dlse=None):
    bn, s_q, d = q.shape
    s_kv = k.shape[1]
    bq, bk = min(block_q, s_q), min(block_k, s_kv)
    n_q, n_kv = s_q // bq, s_kv // bk

    # delta_i = rowsum(dO_i * O_i) — tiny elementwise reduce; let XLA fuse
    # it.  The residual arrays (delta, lse) take the generation-conditional
    # layout (_lse_lane_major): lane-major [bn, 1, s] where the re-layout
    # compiles, sublane-major [bn, s, 1] on v4/unknown — same tradeoff as
    # the forward's lse store.
    lane = _lse_lane_major()
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    if dlse is not None:
        # lse-output cotangent (ring-attention stage merging): with
        # lse = logsumexp(s) an output, ∂lse/∂s_j = p_j adds dlse·p_j to
        # ds — i.e. ds = p·(dp - delta + dlse).  Folding it into delta
        # (delta_eff = delta - dlse) reuses both backward kernels
        # untouched.
        delta = delta - dlse.astype(jnp.float32)
    if lane:
        delta, lse3 = delta[:, None, :], lse[:, None, :]
    else:
        delta, lse3 = delta[:, :, None], lse[:, :, None]

    q_spec_qmajor = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec_qmajor = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    row_spec_qmajor = (
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)) if lane
        else pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)))

    common = [q, k, v, do, lse3, delta]

    def with_mask(kernel, index_map):
        if mask is None:
            return functools.partial(kernel, None), [], []
        n_heads = bn // mask.shape[0]
        spec = pl.BlockSpec((1, 1, bk), functools.partial(index_map, n_heads))
        return kernel, [spec], [mask[:, None, :]]

    # --- dq: grid (bn, q blocks, kv blocks) ---
    kernel, mspec, margs = with_mask(
        _bwd_dq_kernel, lambda h, b, i, j: (b // h, 0, j))
    dq = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_kv=n_kv,
                          lane_lse=lane, precision=precision),
        name="flash_bwd_dq",
        grid=(bn, n_q, n_kv),
        in_specs=mspec + [q_spec_qmajor, kv_spec_qmajor, kv_spec_qmajor,
                          q_spec_qmajor, row_spec_qmajor, row_spec_qmajor],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds(q, q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(      # dq carry: kv dim only
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*margs, *common)

    # --- dk/dv: grid (bn, kv blocks, q blocks) ---
    q_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    row_spec = (pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)) if lane
                else pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)))
    kernel, mspec, margs = with_mask(
        _bwd_dkv_kernel, lambda h, b, j, i: (b // h, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q,
                          lane_lse=lane, precision=precision),
        name="flash_bwd_dkv",
        grid=(bn, n_kv, n_q),
        in_specs=mspec + [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                          row_spec],
        out_specs=[pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))],
        out_shape=[_sds(q, k.shape, k.dtype),
                   _sds(q, v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(      # dk/dv carry: q dim only
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*margs, *common)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, mask, causal, block_q, block_k, interpret, precision):
    out, _ = _flash_fwd(q, k, v, mask, scale=q.shape[-1] ** -0.5,
                        causal=causal, block_q=block_q, block_k=block_k,
                        interpret=interpret, precision=precision)
    return out


def _flash_vjp_fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                   precision):
    out, lse = _flash_fwd(q, k, v, mask, scale=q.shape[-1] ** -0.5,
                          causal=causal, block_q=block_q, block_k=block_k,
                          interpret=interpret, precision=precision)
    return out, (q, k, v, mask, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, precision, res, do):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, mask, out, lse, do,
                            scale=q.shape[-1] ** -0.5, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, precision=precision)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_lse(q, k, v, mask, causal, block_q, block_k, interpret, precision):
    return _flash_fwd(q, k, v, mask, scale=q.shape[-1] ** -0.5,
                      causal=causal, block_q=block_q, block_k=block_k,
                      interpret=interpret, precision=precision)


def _flash_lse_vjp_fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                       precision):
    out, lse = _flash_fwd(q, k, v, mask, scale=q.shape[-1] ** -0.5,
                          causal=causal, block_q=block_q, block_k=block_k,
                          interpret=interpret, precision=precision)
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_lse_vjp_bwd(causal, block_q, block_k, interpret, precision, res,
                       cots):
    q, k, v, mask, out, lse = res
    do, dlse = cots
    dq, dk, dv = _flash_bwd(q, k, v, mask, out, lse, do,
                            scale=q.shape[-1] ** -0.5, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, precision=precision,
                            dlse=dlse)
    return dq, dk, dv, None


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_mha_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  mask: jax.Array | None = None, causal: bool = False,
                  block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K,
                  interpret: bool | None = None,
                  precision=None) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_mha` that also returns the logsumexp rows.

    Returns ``(out [B, S, N, D], lse [B, N, S] f32)``.  The lse output is
    differentiable (its cotangent folds into the backward's delta), which
    is what lets ring attention merge per-stage flash results exactly:
    ``out = Σ_i exp(lse_i - LSE)·out_i`` with both factors carrying
    gradient.  Fully-masked rows report ``lse = NEG_INF`` and zero
    output, so they contribute nothing to a merge.
    """
    if not supported(q, k, block_q, block_k):
        raise ValueError(
            f"flash_mha_lse: shapes q={q.shape} k={k.shape} do not tile "
            f"into block_q={block_q}, block_k={block_k} blocks")
    interpret = kernel_impl.resolve_interpret("flash_attention_lse",
                                              interpret)
    b, s_q, n, d = q.shape

    def fold(x):  # [B, S, N, D] → [B*N, S, D]
        return x.transpose(0, 2, 1, 3).reshape(b * n, x.shape[1], d)

    mask = None if mask is None else mask.astype(jnp.int32)
    out, lse = _flash_lse(fold(q), fold(k), fold(v), mask, causal,
                          block_q, block_k, interpret, precision)
    return (out.reshape(b, n, s_q, d).transpose(0, 2, 1, 3),
            lse.reshape(b, n, s_q))


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              mask: jax.Array | None = None, causal: bool = False,
              block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
              interpret: bool | None = None,
              precision=None) -> jax.Array:
    """Flash multi-head attention.

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]`` (the attention.py layout).
      mask: optional ``[batch, seq_kv]`` key-padding mask, 1 = attend.
      causal: apply a causal (autoregressive) mask; above-diagonal key/value
        blocks are skipped entirely, halving the work.
      interpret: run under the Pallas interpreter (defaults to True off-TPU,
        which is how the CPU test suite executes this kernel).
      precision: forwarded to every dot inside the kernels (fwd, recompute,
        bwd).  None = backend default (bf16 MXU products for f32 inputs on
        TPU); lax.Precision.HIGHEST requests multi-pass f32 — whether
        Mosaic honors it on-chip is probed by perf/exp_precision_probe.py.

    Returns ``[batch, seq, heads, head_dim]`` attention output in q's dtype.
    """
    if not supported(q, k, block_q, block_k):
        raise ValueError(
            f"flash_mha: shapes q={q.shape} k={k.shape} do not tile into "
            f"block_q={block_q}, block_k={block_k} blocks; use "
            f"tpuframe.ops.attention.multihead_attention for the fallback")
    interpret = kernel_impl.resolve_interpret("flash_attention", interpret)
    b, s_q, n, d = q.shape
    s_kv = k.shape[1]

    def fold(x):  # [B, S, N, D] → [B*N, S, D]
        return x.transpose(0, 2, 1, 3).reshape(b * n, x.shape[1], d)

    mask = None if mask is None else mask.astype(jnp.int32)
    out = _flash(fold(q), fold(k), fold(v), mask, causal,
                 block_q, block_k, interpret, precision)
    return out.reshape(b, n, s_q, d).transpose(0, 2, 1, 3)
