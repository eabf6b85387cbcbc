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

Tiling.  A grid step costs a v5e about 0.4 µs whatever it computes, and a
128 × 128 score tile at head size 64 is 21 ns of products: at that tiling
the kernels ran at 3% of their roofline and the step count was the whole
of their time (PERF.md §6, PR 26).  So the tiling has two levels and is
chosen from the shape (:func:`choose_tiles`).  The *block* is what one
grid step owns and the pipeline moves: a block of query rows, and as much
of K and V as the VMEM budget allows — the whole of them where they fit,
so that they are fetched once per (batch, head).  Inside a step a
``lax.fori_loop`` walks the block in *sub-blocks*, the score tile that is
live at once; under a causal mask it runs only as far as the diagonal
needs, so no step and no product is spent above it.  Where the grid still
holds a step above the diagonal (K/V not resident), its index map is
clamped to the last block its row needs and the pipeline fetches nothing
for it.  Accumulation is float32 regardless of input dtype (bf16 inputs
keep bf16 in HBM, f32 in VMEM).

Used through :func:`tpuframe.ops.attention.multihead_attention` with
``impl="pallas"`` (or ``TPUFRAME_ATTN_IMPL=pallas``); CPU tests run the same
kernel under the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuframe.ops import kernel_impl
from tpuframe.tune import db as _tune_db  # stdlib-only module
from tpuframe.tune import roofline as _roofline

# Blocks a caller did not give: TPUFRAME_FA_BLOCK_Q/K, else a *measured*
# tuning-DB row (only under TPUFRAME_TUNE_GEN), else None — the shape rule
# below.  An override is one (block_q, block_k) for all three kernels, as
# an explicit ``block_q=``/``block_k=`` argument is.
_BLOCK_Q_OVERRIDE, _BLOCK_K_OVERRIDE = _tune_db.resolve_fa_blocks(None, None)
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


# ---------------------------------------------------------------------------
# tiling: chosen from the shape
# ---------------------------------------------------------------------------

# What one kernel instance may hold in VMEM by the arithmetic of
# :func:`vmem_bytes`: a fifth of a v5e core's 128 MiB.  Mosaic's scoped
# default is 16 MiB; a tiling whose arithmetic passes that gets
# ``vmem_limit_bytes`` raised to the arithmetic's figure and no further
# (the arithmetic counts every score-sized tile of an inner step as live
# and the row statistics at their wider layout, so it errs upward).
VMEM_BUDGET = 24 * 1024 * 1024
_SCOPED_DEFAULT = 16 * 1024 * 1024

_KERNELS = ("fwd", "dq", "dkv")
# (own, sub) caps: the rows (fwd, dq) or columns (dkv) a grid step owns
# beside the operand its inner loop walks, and how much of that operand one
# inner step takes — the edge of the score tile live at once.  Read on the
# v5e at [96, 2048, 64], [24, 8192, 64] and [48, 2048, 128] causal bf16
# (perf/flash_tile_sweep.py; PERF.md §6, PR 26).  The forward pays two
# cross-lane reductions (row max, row sum) per row of every inner step,
# however narrow the step: it wants the widest tile.  The backward kernels
# have none and are fastest at 512.
_CAPS = {"fwd": (1024, 1024), "dq": (512, 512), "dkv": (1024, 512)}


class Tiles(NamedTuple):
    """One kernel's tiling.  ``sub`` divides ``block_k`` (fwd, dq: the
    inner loop walks K/V) or ``block_q`` (dkv: it walks Q/dO)."""
    block_q: int
    block_k: int
    sub: int


# The three kernel launchers below are jitted on their own with everything
# but the arrays static: a model's layers share one shape, so the kernel is
# traced and lowered to Mosaic once per program and not once per layer —
# tracing and lowering are paid at every process start, cached executable
# or not (PERF.md §6, PR 26: 36 launches cost the LM cell's set-up 8 s).
# (``lane``, the row statistics' layout, is static too: it is read from the
# environment, which a jit cache key does not see.)
_STATIC = ("scale", "causal", "tiles", "lane", "interpret", "precision",
           "window", "group")


def _blocks_of(seq: int) -> list[int]:
    """The blocks that tile ``seq``, largest first: its divisors that are
    multiples of 128, the whole sequence among them; a sequence that is no
    multiple of 128 tiles only as a whole (supported() takes it only when
    it is shorter than 128)."""
    if seq % _LANES:
        return [seq]
    return [seq // n for n in range(1, seq // _LANES + 1)
            if seq % n == 0 and (seq // n) % _LANES == 0]


def _fits(seq: int, cap: int) -> int:
    """Largest block of ``seq`` that is at most ``cap``, or the smallest
    there is."""
    blocks = _blocks_of(seq)
    return next((c for c in blocks if c <= cap), blocks[-1])


def vmem_bytes(kernel: str, tiles: Tiles, head_dim: int,
               itemsize: int, rope_dim: int = 0) -> int:
    """VMEM one grid step of ``kernel`` holds at ``tiles``: every blocked
    operand and result twice (the pipeline double-buffers them), the f32
    accumulators, the row statistics at their wider (sublane-major, 128
    lanes a row) layout, and the score-sized tiles live inside one inner
    step — s and p forward, p, dp and ds backward, in f32, plus the copy
    cast to the operands' dtype for the second product.  ``rope_dim``: the
    width of a second score term's operands (latent attention), which come
    with their own gradients and accumulators."""
    bq, bk, sub = tiles
    d = -(-head_dim // _LANES) * _LANES       # the minor dim pads to lanes
    q_tile, k_tile = bq * d * itemsize, bk * d * itemsize
    d_r = -(-rope_dim // _LANES) * _LANES
    qr_tile, kr_tile = bq * d_r * itemsize, bk * d_r * itemsize
    stat = bq * _LANES * 4
    if kernel == "fwd":
        blocked = 2 * q_tile + 2 * k_tile + stat        # q, o; k, v; lse
        blocked += qr_tile + kr_tile
        scratch = bq * d * 4 + 2 * stat                 # acc; m, l
        live = bq * sub * (2 * 4 + itemsize)
    elif kernel == "dq":
        blocked = 3 * q_tile + 2 * k_tile + 2 * stat    # q, do, dq; k, v
        blocked += 2 * qr_tile + kr_tile                # q_rope, its dq
        scratch = bq * (d + d_r) * 4
        live = bq * sub * (3 * 4 + itemsize)
    else:
        blocked = 2 * q_tile + 4 * k_tile + 2 * stat    # q, do; k, v, dk, dv
        blocked += qr_tile + 2 * kr_tile                # k_rope, its dk
        scratch = bk * (2 * d + d_r) * 4
        live = sub * bk * (3 * 4 + itemsize)
    return 2 * blocked + scratch + live


def _compiler_params(kernel: str, tiles: Tiles, head_dim: int,
                     itemsize: int, rope_dim: int = 0) -> pltpu.CompilerParams:
    """The leading two grid dims carry no state from step to step (the
    accumulators live on the last): declaring them parallel lets Mosaic
    schedule and pipeline them freely.  The VMEM limit stays Mosaic's own
    unless the arithmetic says this tiling needs more."""
    need = vmem_bytes(kernel, tiles, head_dim, itemsize, rope_dim)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > _SCOPED_DEFAULT else None)


def choose_tiles(kernel: str, s_q: int, s_kv: int, head_dim: int,
                 itemsize: int = 2, rope_dim: int = 0) -> Tiles:
    """The tiling of ``kernel`` ("fwd", "dq" or "dkv") for this shape.

    The operand the inner loop walks — K and V forward and in dq, Q and dO
    in dkv — takes the largest block that divides its sequence and fits
    :data:`VMEM_BUDGET`: the whole sequence where that fits, so the grid
    has no steps above the diagonal at all.  The other side takes the
    largest block up to the kernel's cap in ``_CAPS`` and the loop steps by
    up to its sub cap; both halve before the walked block does, down to 128.
    A causal mask does not enter: the inner loop stops at the diagonal
    whatever the blocks are."""
    own_len, walked_len = (s_kv, s_q) if kernel == "dkv" else (s_q, s_kv)

    def tiles(own, walked, sub):
        return (Tiles(walked, own, sub) if kernel == "dkv"
                else Tiles(own, walked, sub))

    cap_own, cap_sub = _CAPS[kernel]
    while True:
        own = _fits(own_len, cap_own)
        for walked in _blocks_of(walked_len):
            t = tiles(own, walked, _fits(walked, cap_sub))
            if vmem_bytes(kernel, t, head_dim, itemsize,
                          rope_dim) <= VMEM_BUDGET:
                return t
        if cap_own <= _LANES and cap_sub <= _LANES:
            return t            # the smallest there is
        if cap_sub >= cap_own:
            cap_sub //= 2
        else:
            cap_own //= 2


def _tiling(s_q: int, s_kv: int, head_dim: int, itemsize: int,
            block_q: int | None, block_k: int | None,
            rope_dim: int = 0) -> tuple:
    """Tiles of (fwd, dq, dkv).  A block the caller gave (or the override
    above) holds for all three kernels; the rule fills what is left."""
    block_q = block_q or _BLOCK_Q_OVERRIDE
    block_k = block_k or _BLOCK_K_OVERRIDE
    out = []
    for kernel in _KERNELS:
        bq, bk, _ = choose_tiles(kernel, s_q, s_kv, head_dim, itemsize,
                                 rope_dim)
        bq = min(block_q, s_q) if block_q else bq
        bk = min(block_k, s_kv) if block_k else bk
        walked = bq if kernel == "dkv" else bk
        out.append(Tiles(bq, bk, _fits(walked, _CAPS[kernel][1])))
    return tuple(out)


def _sds(like: jax.Array, shape, dtype) -> jax.ShapeDtypeStruct:
    """out_shape that inherits ``like``'s varying-mesh-axes, so the kernel
    works unchanged inside ``shard_map`` (where jax requires outputs to
    declare their vma) and outside it (empty vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def supported(q: jax.Array, k: jax.Array | None = None,
              block_q: int | None = None,
              block_k: int | None = None) -> bool:
    """True when shapes fit the kernel's static tiling (else caller falls
    back to the XLA einsum path, tpuframe.ops.attention)."""
    if q.ndim != 4:
        return False
    _, s_q, _, d = q.shape
    s_kv = s_q if k is None else k.shape[1]
    # seq dims must tile into whole blocks — multiples of 128, or one block
    # of a shorter sequence — and stay sublane-aligned (mult of 8); head dim
    # beyond 256 would blow the per-block VMEM budget.
    if d > 256 or s_q % 8 or s_kv % 8:
        return False
    return _tiles_whole(_tiling(s_q, s_kv, d, q.dtype.itemsize, block_q,
                                block_k), s_q, s_kv)


def _tiles_whole(tiling, s_q: int, s_kv: int) -> bool:
    """Every kernel's blocks divide their sequence and are whole lanes (or
    the one block of a sequence shorter than a lane row)."""
    return all(s % b == 0 and (b < _LANES or b % _LANES == 0)
               for t in tiling
               for s, b in ((s_q, t.block_q), (s_kv, t.block_k)))


# ---------------------------------------------------------------------------
# the causal geometry, shared by the three kernels
# ---------------------------------------------------------------------------


def _div(x, n: int):
    """``x // n`` for a traced ``x`` that is never negative: the truncating
    divide, without the sign correction ``//`` lowers for every use."""
    return jax.lax.div(x, jnp.int32(n))


def _k_ranges(causal, row0, n_rows, col0, sub, n_sub, window=None):
    """Of the ``n_sub`` sub-blocks of ``sub`` columns from ``col0``, what
    rows [row0, row0 + n_rows) need: ``(n_first, n_lo, n_plain, n_need)``.
    Sub-blocks [n_lo, n_plain) lie wholly on or below the diagonal and
    wholly inside the window (no per-element mask); [n_first, n_lo)
    straddle the window's far edge and [n_plain, n_need) the diagonal;
    those before ``n_first`` (every row's window has passed them) and from
    ``n_need`` on (above the diagonal) are never touched.  Non-causal: all
    plain.  Without a window ``n_first`` and ``n_lo`` are the static 0."""
    if not causal:
        return 0, 0, n_sub, n_sub
    n_plain = jnp.minimum(_div(jnp.maximum(row0 + 1 - col0, 0), sub), n_sub)
    n_need = jnp.minimum(
        _div(jnp.maximum(row0 + n_rows - col0, 0) + sub - 1, sub), n_sub)
    if window is None:
        return 0, 0, n_plain, n_need
    # row i sees columns (i - window, i]: the first row's window opens at
    # row0 - window + 1, the last row's at row0 + n_rows - window
    n_first = jnp.minimum(
        _div(jnp.maximum(row0 - window + 1 - col0, 0), sub), n_need)
    n_lo = jnp.minimum(
        _div(jnp.maximum(row0 + n_rows - window - col0, 0) + sub - 1, sub),
        n_need)
    return n_first, n_lo, jnp.maximum(n_plain, n_lo), n_need


def _q_ranges(causal, col0, n_cols, row0, sub, n_sub, window=None):
    """The dkv kernel's view: of the ``n_sub`` sub-blocks of ``sub`` rows
    from ``row0``, what columns [col0, col0 + n_cols) reach:
    ``(t_first, t_plain, t_hi, t_end)``.  Sub-blocks [t_first, t_plain)
    straddle the diagonal, [t_plain, t_hi) lie wholly on or below it and
    wholly inside the window, [t_hi, t_end) straddle the window's far
    edge; those before ``t_first`` lie above the diagonal and from
    ``t_end`` on past every column's window.  Non-causal: all plain.
    Without a window ``t_hi`` and ``t_end`` are the static ``n_sub``."""
    if not causal:
        return 0, 0, n_sub, n_sub
    t_first = jnp.minimum(_div(jnp.maximum(col0 - row0, 0), sub), n_sub)
    t_plain = jnp.minimum(
        _div(jnp.maximum(col0 + n_cols - 1 - row0, 0) + sub - 1, sub), n_sub)
    if window is None:
        return t_first, t_plain, n_sub, n_sub
    # column j is seen by rows [j, j + window): the first column's last
    # row is col0 + window - 1, the last column's col0 + n_cols + window - 2
    t_end = jnp.clip(
        _div(jnp.maximum(col0 + n_cols + window - 1 - row0, 0) + sub - 1,
             sub), t_first, n_sub)
    t_plain = jnp.minimum(t_plain, t_end)
    t_hi = jnp.clip(_div(jnp.maximum(col0 + window - row0, 0), sub),
                    t_plain, t_end)
    return t_first, t_plain, t_hi, t_end


def _sub_loop(lo, hi, body):
    """``body(t)`` for t in [lo, hi).  Static bounds of no or one iteration
    leave no loop in the kernel; everything else is a ``fori_loop``, never
    unrolled (a kernel's compile time is set-up time)."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        if hi > lo:
            body(lo)
        return
    jax.lax.fori_loop(lo, hi, lambda t, _: body(t), None)


def _at(t, sub):
    """Offset of sub-block ``t``, with its alignment said."""
    return t * sub if isinstance(t, int) else pl.multiple_of(t * sub, sub)


def _keep(mask_row, need_tri, row0, col0, shape, window=None):
    """[rows, cols] bool of the positions that attend, or None for all.
    ``need_tri`` asks for the causal geometry: the triangle and, where
    there is a window, its far edge."""
    keep = None
    if mask_row is not None:
        keep = jnp.broadcast_to(mask_row != 0, shape)
    if need_tri:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        tri = row0 + rows >= col0 + cols
        if window is not None:
            tri = jnp.logical_and(tri, row0 + rows - window < col0 + cols)
        keep = tri if keep is None else jnp.logical_and(keep, tri)
    return keep


def _k_block_of(causal, block_q, block_k, n_kv, window=None):
    """``f(i, j)``: the K/V block that grid step (q block i, kv step j)
    fetches.  Above the diagonal that is the last block row i needs, not
    block j: the pipeline sees an unchanged index and issues no DMA for a
    step that computes nothing.  Likewise before the first block a window
    leaves row i."""
    if not causal:
        return lambda i, j: j
    last = lambda i: jnp.minimum(  # noqa: E731
        _div((i + 1) * block_q - 1, block_k), n_kv - 1)
    if window is None:
        return lambda i, j: jnp.minimum(j, last(i))
    return lambda i, j: jnp.clip(
        j, _div(jnp.maximum(i * block_q - window + 1, 0), block_k), last(i))


def _q_block_of(causal, block_q, block_k, n_q, window=None):
    """``f(j, i)``: the dkv kernel's mirror of :func:`_k_block_of` — before
    the diagonal, the first Q block that reaches K/V block j; past the
    window, the last."""
    if not causal:
        return lambda j, i: i
    first = lambda j: jnp.minimum(  # noqa: E731
        _div(j * block_k, block_q), n_q - 1)
    if window is None:
        return lambda j, i: jnp.maximum(i, first(j))
    return lambda j, i: jnp.clip(
        i, first(j),
        jnp.minimum(_div((j + 1) * block_k + window - 2, block_q), n_q - 1))


def _kv_head_of(group: int):
    """``f(b)``: the folded K/V head that folded query head ``b`` reads.
    Heads fold batch-major, so ``group`` consecutive query heads share one
    K/V head and K and V are never repeated in HBM."""
    if group == 1:
        return lambda b: b
    return lambda b: _div(b, group)


def _window_kw(window) -> dict:
    """The kernels' ``window`` keyword, left out where there is none."""
    return {} if window is None else {"window": window}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                 # outputs
                acc_ref, m_ref, l_ref,          # scratch
                *, scale: float, causal: bool, tiles: Tiles,
                n_kv: int, lane_lse: bool = False, precision=None,
                window: int | None = None, rope=None):
    """``rope``: the refs ``(q_rope, k_rope)`` of a second score term
    (latent attention, below), summed with the first before the softmax."""
    block_q, block_k, sub = tiles
    qi = pl.program_id(1)
    kv = pl.program_id(2)
    row0, col0 = qi * block_q, kv * block_k

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(t, need_tri):
        c = _at(t, sub)
        s = _qk(q_ref[0], k_ref[0, pl.ds(c, sub), :], precision,  # [bq, sub]
                rope and (rope[0][0], rope[1][0, pl.ds(c, sub), :])) * scale
        keep = _keep(None if mask_ref is None else mask_ref[0, t],
                     need_tri, row0, col0 + c, s.shape, window)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # rescale factor
        p = jnp.exp(s - m_new)                            # [bq, sub]
        if keep is not None:
            # Explicit zeroing (not exp-underflow): a fully-masked row keeps
            # l == 0 and yields zero output + NEG_INF lse, and the backward
            # recompute below reproduces exactly p == 0 for it.
            p = jnp.where(keep, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, pl.ds(c, sub), :],
            (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    n_first, n_lo, n_plain, n_need = _k_ranges(
        causal, row0, block_q, col0, sub, block_k // sub, window)
    _sub_loop(n_first, n_lo, lambda t: step(t, True))
    _sub_loop(n_lo, n_plain, lambda t: step(t, False))
    _sub_loop(n_plain, n_need, lambda t: step(t, True))

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


def _mask_operand(mask, tiles: Tiles, n_heads: int, k_block):
    """The [B, S_kv] key mask as an operand of a kernel whose inner loop
    walks K: [B, S_kv / sub, 1, sub], so that sub-block ``t`` of a block is
    ``ref[0, t]`` — a dynamic index on a leading dim, never a dynamic lane
    slice.  ``k_block(i, j)`` is the K block of a grid step."""
    _, bk, sub = tiles
    spec = pl.BlockSpec(
        (1, bk // sub, 1, sub),
        lambda b, i, j: (b // n_heads, k_block(i, j), 0, 0))
    return spec, mask.reshape(mask.shape[0], mask.shape[1] // sub, 1, sub)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_fwd(q, k, v, mask, *, scale, causal, tiles: Tiles, lane: bool,
               interpret, precision=None, window=None, group=1):
    bn, s_q, d = q.shape
    s_kv = k.shape[1]
    bq, bk, _ = tiles
    n_q, n_kv = s_q // bq, s_kv // bk

    k_block = _k_block_of(causal, bq, bk, n_kv, window)
    kv_head = _kv_head_of(group)
    kv_spec = pl.BlockSpec(
        (1, bk, d), lambda b, i, j: (kv_head(b), k_block(i, j), 0))
    in_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                kv_spec, kv_spec]
    args = [q, k, v]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, tiles=tiles, n_kv=n_kv,
        lane_lse=lane, precision=precision, **_window_kw(window))
    if mask is None:
        kernel = functools.partial(kernel, None)
    else:
        spec, arg = _mask_operand(mask, tiles, bn // mask.shape[0], k_block)
        in_specs.insert(0, spec)
        args.insert(0, arg)

    lse_spec = (pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)) if lane
                else pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)))
    lse_shape = (bn, 1, s_q) if lane else (bn, s_q, 1)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",   # the op's name in a profiler trace
        grid=(bn, n_q, n_kv),
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
        compiler_params=_compiler_params("fwd", tiles, d, q.dtype.itemsize),
        interpret=interpret,
    )(*args)
    return out, (lse[:, 0, :] if lane else lse[:, :, 0])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _rows(ref_block, lane_lse):
    """A block of a row statistic as a [rows, 1] column."""
    return ref_block.reshape(-1, 1) if lane_lse else ref_block


def _qk(q, k, precision, rope=None):
    """``q k^T`` in f32, plus ``rope``'s ``(q_rope, k_rope)`` product
    where the scores have a second term."""
    dot = lambda a, b: jax.lax.dot_general(  # noqa: E731
        a, b, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    return dot(q, k) if not rope else dot(q, k) + dot(*rope)


def _recompute_p(q, k, lse, keep, *, scale, precision, rope=None):
    """Rebuild the probability tile from the saved logsumexp (f32)."""
    s = _qk(q, k, precision, rope) * scale
    if keep is None:
        return jnp.exp(s - lse)
    p = jnp.exp(jnp.where(keep, s, NEG_INF) - lse)
    return jnp.where(keep, p, 0.0)                          # see fwd kernel


def _bwd_dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, tiles: Tiles, n_kv,
                   lane_lse=False, precision=None, window=None, rope=None):
    """``rope``: the refs ``(q_rope, k_rope, dq_rope, its accumulator)``
    of a second score term."""
    block_q, block_k, sub = tiles
    qi = pl.program_id(1)
    kv = pl.program_id(2)
    row0, col0 = qi * block_q, kv * block_k

    @pl.when(kv == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if rope:
            rope[3][...] = jnp.zeros_like(rope[3])

    def step(t, need_tri):
        c = _at(t, sub)
        k = k_ref[0, pl.ds(c, sub), :]
        kr = rope and rope[1][0, pl.ds(c, sub), :]
        keep = _keep(None if mask_ref is None else mask_ref[0, t],
                     need_tri, row0, col0 + c, (block_q, sub), window)
        p = _recompute_p(q_ref[0], k, _rows(lse_ref[0], lane_lse), keep,
                         scale=scale, precision=precision,
                         rope=rope and (rope[0][0], kr))
        dp = jax.lax.dot_general(                       # dO @ V^T [bq, sub]
            do_ref[0], v_ref[0, pl.ds(c, sub), :], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        ds = p * (dp - _rows(delta_ref[0], lane_lse))   # [bq, sub]
        dq_acc[...] += scale * jax.lax.dot_general(     # ds @ K    [bq, d]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        if rope:
            rope[3][...] += scale * jax.lax.dot_general(
                ds.astype(kr.dtype), kr, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)

    n_first, n_lo, n_plain, n_need = _k_ranges(
        causal, row0, block_q, col0, sub, block_k // sub, window)
    _sub_loop(n_first, n_lo, lambda t: step(t, True))
    _sub_loop(n_lo, n_plain, lambda t: step(t, False))
    _sub_loop(n_plain, n_need, lambda t: step(t, True))

    @pl.when(kv == n_kv - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        if rope:
            rope[2][0] = rope[3][...].astype(rope[2].dtype)


def _bwd_dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, tiles: Tiles, n_q,
                    lane_lse=False, precision=None, window=None, group=1,
                    rope=None):
    """``rope``: the refs ``(q_rope, k_rope, dk_rope, its accumulator)`` of
    a second score term whose key the ``group`` heads share: K and V are
    then each head's own, and what sums over the group is dk_rope."""
    block_q, block_k, sub = tiles
    kv = pl.program_id(1)
    step_i = pl.program_id(2)
    # the last grid dim walks the Q blocks of each of the ``group`` query
    # heads that read this K/V head in turn, and dK and dV sum over them
    qi = step_i if group == 1 else jax.lax.rem(step_i, jnp.int32(n_q))
    row0, col0 = qi * block_q, kv * block_k
    if rope:
        @pl.when(step_i == 0)
        def _():
            rope[3][...] = jnp.zeros_like(rope[3])

    # dK and dV sum over the whole group, or, beside a shared rope key,
    # over one head
    @pl.when(qi == 0 if rope else step_i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(t, need_tri):
        r = _at(t, sub)
        q = q_ref[0, pl.ds(r, sub), :]
        do = do_ref[0, pl.ds(r, sub), :]
        if lane_lse:      # [block_q / sub, 1, sub] blocks: see _flash_bwd_dkv
            lse, delta = lse_ref[0, t], delta_ref[0, t]
        else:
            lse = lse_ref[0, pl.ds(r, sub), :]
            delta = delta_ref[0, pl.ds(r, sub), :]
        keep = _keep(None if mask_ref is None else mask_ref[0],
                     need_tri, row0 + r, col0, (sub, block_k), window)
        qr = rope and rope[0][0, pl.ds(r, sub), :]
        p = _recompute_p(q, k_ref[0], _rows(lse, lane_lse), keep,
                         scale=scale, precision=precision,  # [sub, bk]
                         rope=rope and (qr, rope[1][0]))
        dv_acc[...] += jax.lax.dot_general(             # P^T @ dO  [bk, d]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        ds = p * (dp - _rows(delta, lane_lse))
        dk_acc[...] += scale * jax.lax.dot_general(     # ds^T @ Q  [bk, d]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        if rope:
            rope[3][...] += scale * jax.lax.dot_general(
                ds.astype(qr.dtype), qr, (((0,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)

    n_sub = block_q // sub
    t_first, t_plain, t_hi, t_end = _q_ranges(causal, col0, block_k, row0,
                                              sub, n_sub, window)
    _sub_loop(t_first, t_plain, lambda t: step(t, True))
    _sub_loop(t_plain, t_hi, lambda t: step(t, False))
    _sub_loop(t_hi, t_end, lambda t: step(t, True))

    @pl.when(qi == n_q - 1 if rope else step_i == group * n_q - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if rope:
        @pl.when(step_i == group * n_q - 1)
        def _():
            rope[2][0] = rope[3][...].astype(rope[2].dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_dq(q, k, v, mask, do, lse, delta, *, scale, causal,
                  tiles: Tiles, lane: bool, interpret, precision=None,
                  window=None, group=1):
    """dq: grid (bn, q blocks, kv blocks), the inner loop walks K/V."""
    bn, s_q, d = q.shape
    bq, bk, _ = tiles
    n_q, n_kv = s_q // bq, k.shape[1] // bk

    k_block = _k_block_of(causal, bq, bk, n_kv, window)
    kv_head = _kv_head_of(group)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (1, bk, d), lambda b, i, j: (kv_head(b), k_block(i, j), 0))
    if lane:
        row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        rows = [lse[:, None, :], delta[:, None, :]]
    else:
        row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
        rows = [lse[:, :, None], delta[:, :, None]]
    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, tiles=tiles, n_kv=n_kv,
        lane_lse=lane, precision=precision, **_window_kw(window))
    mspec, margs = [], []
    if mask is None:
        kernel = functools.partial(kernel, None)
    else:
        spec, arg = _mask_operand(mask, tiles, bn // mask.shape[0], k_block)
        mspec, margs = [spec], [arg]
    return pl.pallas_call(
        kernel,
        name="flash_bwd_dq",
        grid=(bn, n_q, n_kv),
        in_specs=mspec + [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                          row_spec],
        out_specs=q_spec,
        out_shape=_sds(q, q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params("dq", tiles, d, q.dtype.itemsize),
        interpret=interpret,
    )(*margs, q, k, v, do, *rows)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_dkv(q, k, v, mask, do, lse, delta, *, scale, causal,
                   tiles: Tiles, lane: bool, interpret, precision=None,
                   window=None, group=1):
    """dk/dv: grid (K/V heads, kv blocks, q blocks of each query head of
    the group in turn), the inner loop walks Q/dO."""
    bn, s_q, d = q.shape
    bn_kv = k.shape[0]
    bq, bk, sub = tiles
    n_q, n_kv = s_q // bq, k.shape[1] // bk

    q_block = _q_block_of(causal, bq, bk, n_q, window)
    if group == 1:
        q_at = lambda b, j, i: (b, q_block(j, i))  # noqa: E731
        kernel_kw = _window_kw(window)
    else:
        q_at = lambda b, j, i: (  # noqa: E731
            b * group + _div(i, n_q),
            q_block(j, jax.lax.rem(i, jnp.int32(n_q))))
        kernel_kw = dict(_window_kw(window), group=group)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (*q_at(b, j, i), 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    if lane:
        # [bn, s_q / sub, 1, sub]: sub-block t of a block is ref[0, t],
        # lane-major as it is used (the key mask's layout in the other two
        # kernels, for the same reason)
        row_spec = pl.BlockSpec((1, bq // sub, 1, sub),
                                lambda b, j, i: (*q_at(b, j, i), 0, 0))
        rows = [x.reshape(bn, s_q // sub, 1, sub) for x in (lse, delta)]
    else:
        row_spec = pl.BlockSpec((1, bq, 1),
                                lambda b, j, i: (*q_at(b, j, i), 0))
        rows = [lse[:, :, None], delta[:, :, None]]
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, tiles=tiles, n_q=n_q,
        lane_lse=lane, precision=precision, **kernel_kw)
    mspec, margs = [], []
    if mask is None:
        kernel = functools.partial(kernel, None)
    else:
        n_heads = bn_kv // mask.shape[0]
        mspec = [pl.BlockSpec((1, 1, bk),
                              lambda b, j, i: (b // n_heads, 0, j))]
        margs = [mask[:, None, :]]
    return pl.pallas_call(
        kernel,
        name="flash_bwd_dkv",
        grid=(bn_kv, n_kv, group * n_q),
        in_specs=mspec + [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                          row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[_sds(q, k.shape, k.dtype),
                   _sds(q, v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params("dkv", tiles, d, q.dtype.itemsize),
        interpret=interpret,
    )(*margs, q, k, v, do, *rows)


def _flash_bwd(q, k, v, mask, out, lse, do, *, scale, causal, tiling,
               interpret, precision=None, dlse=None, window=None, group=1):
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise reduce; let XLA fuse
    # it.  The residual arrays (delta, lse) take the generation-conditional
    # layout (_lse_lane_major): lane-major where the re-layout compiles,
    # sublane-major [bn, s, 1] on v4/unknown — same tradeoff as the
    # forward's lse store.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    if dlse is not None:
        # lse-output cotangent (ring-attention stage merging): with
        # lse = logsumexp(s) an output, ∂lse/∂s_j = p_j adds dlse·p_j to
        # ds — i.e. ds = p·(dp - delta + dlse).  Folding it into delta
        # (delta_eff = delta - dlse) reuses both backward kernels
        # untouched.
        delta = delta - dlse.astype(jnp.float32)
    _, dq_tiles, dkv_tiles = tiling
    common = dict(scale=scale, causal=causal, lane=_lse_lane_major(),
                  interpret=interpret, precision=precision,
                  **_geometry_kw(window, group))
    dq = _flash_bwd_dq(q, k, v, mask, do, lse, delta, tiles=dq_tiles,
                       **common)
    dk, dv = _flash_bwd_dkv(q, k, v, mask, do, lse, delta, tiles=dkv_tiles,
                            **common)
    return dq, dk, dv


def _geometry_kw(window, group) -> dict:
    """The launchers' ``window`` and ``group`` keywords, each left out at
    its default, so that a call with neither is the jitted launch it has
    always been."""
    kw = _window_kw(window)
    if group != 1:
        kw["group"] = group
    return kw


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


class _Static(NamedTuple):
    """What the kernels are specialised on besides the arrays' shapes."""
    causal: bool
    tiling: tuple
    interpret: bool
    precision: object
    window: int | None = None   # key j attends iff 0 <= i - j < window
    group: int = 1              # query heads to a K/V head


def _fwd(q, k, v, mask, st: _Static):
    return _flash_fwd(q, k, v, mask, scale=q.shape[-1] ** -0.5,
                      causal=st.causal, tiles=st.tiling[0],
                      lane=_lse_lane_major(), interpret=st.interpret,
                      precision=st.precision,
                      **_geometry_kw(st.window, st.group))


def _bwd(st: _Static, res, do, dlse=None):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, mask, out, lse, do,
                            scale=q.shape[-1] ** -0.5, causal=st.causal,
                            tiling=st.tiling, interpret=st.interpret,
                            precision=st.precision, dlse=dlse,
                            window=st.window, group=st.group)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, mask, st):
    return _fwd(q, k, v, mask, st)[0]


def _flash_vjp_fwd(q, k, v, mask, st):
    out, lse = _fwd(q, k, v, mask, st)
    return out, (q, k, v, mask, out, lse)


_flash.defvjp(_flash_vjp_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_lse(q, k, v, mask, st):
    return _fwd(q, k, v, mask, st)


def _flash_lse_vjp_fwd(q, k, v, mask, st):
    out, lse = _fwd(q, k, v, mask, st)
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_lse_vjp_bwd(st, res, cots):
    return _bwd(st, res, *cots)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _prepare(op, q, k, mask, block_q, block_k, interpret, causal=False,
             precision=None, window=None):
    """What both entry points do before the kernels: refuse a shape that
    does not tile, choose the tiling, resolve the implementation and
    record it with the tiling and the grids that engaged."""
    if not supported(q, k, block_q, block_k):
        raise ValueError(
            f"{op}: shapes q={q.shape} k={k.shape} do not tile into "
            f"block_q={block_q}, block_k={block_k} blocks; use "
            f"tpuframe.ops.attention.multihead_attention for the fallback")
    b, s_q, n, d = q.shape
    s_kv, n_kv = k.shape[1], k.shape[2]
    if n % n_kv:
        raise ValueError(f"{op}: {n} query heads do not share {n_kv} K/V "
                         f"heads evenly")
    if window is not None and not (causal and window > 0):
        raise ValueError(f"{op}: a window of {window} needs causal=True and "
                         f"at least one key")
    if window is not None and window >= s_kv:
        window = None        # every key below the diagonal is inside it
    tiling = _tiling(s_q, s_kv, d, q.dtype.itemsize, block_q, block_k)
    said = "; ".join(
        f"{name} q{t.block_q} k{t.block_k} sub{t.sub} grid "
        f"{b * n}x{s_q // t.block_q}x{s_kv // t.block_k}"
        for name, t in zip(_KERNELS, tiling))
    name = op.replace("flash_mha", "flash_attention")
    if window is not None or n != n_kv:
        said += f"; window {window}, {n // n_kv} query heads a K/V head"
        if window is not None:
            name = name.replace("flash_attention", "flash_window_attention")
    interpret = kernel_impl.resolve_interpret(name, interpret, detail=said)
    mask = None if mask is None else mask.astype(jnp.int32)
    return mask, _Static(causal, tiling, interpret, precision, window,
                         n // n_kv)


def _fold(x):  # [B, S, N, D] → [B*N, S, D]
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def flash_mha_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  mask: jax.Array | None = None, causal: bool = False,
                  block_q: int | None = None,
                  block_k: int | None = None,
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
    mask, st = _prepare("flash_mha_lse", q, k, mask, block_q, block_k,
                        interpret, causal, precision)
    b, s_q, n, d = q.shape
    out, lse = _flash_lse(_fold(q), _fold(k), _fold(v), mask, st)
    return (out.reshape(b, n, s_q, d).transpose(0, 2, 1, 3),
            lse.reshape(b, n, s_q))


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              mask: jax.Array | None = None, causal: bool = False,
              window: int | None = None,
              block_q: int | None = None, block_k: int | None = None,
              interpret: bool | None = None,
              precision=None) -> jax.Array:
    """Flash multi-head attention.

    Args:
      q: ``[batch, seq, heads, head_dim]`` (the attention.py layout).
      k, v: ``[batch, seq_kv, kv_heads, head_dim]``; ``kv_heads`` divides
        ``heads``, and query head ``h`` reads K/V head ``h // (heads /
        kv_heads)`` straight from HBM (grouped-query attention; K and V are
        never repeated), dK and dV summing their group inside the kernel.
      mask: optional ``[batch, seq_kv]`` key-padding mask, 1 = attend.
      causal: apply a causal (autoregressive) mask; what lies above the
        diagonal is never computed, halving the work.
      window: with ``causal``, key ``j`` attends to query ``i`` only while
        ``i - j < window`` (sliding-window attention); blocks wholly past
        the window are neither fetched nor computed, in all three kernels.
      block_q, block_k: what one grid step owns, for all three kernels;
        left out, :func:`choose_tiles` picks them per kernel from the
        shape (unless ``TPUFRAME_FA_BLOCK_Q/K`` or a measured tuning-DB row
        names them).
      interpret: run under the Pallas interpreter (defaults to True off-TPU,
        which is how the CPU test suite executes this kernel).
      precision: forwarded to every dot inside the kernels (fwd, recompute,
        bwd).  None = backend default (bf16 MXU products for f32 inputs on
        TPU); lax.Precision.HIGHEST requests multi-pass f32 — whether
        Mosaic honors it on-chip is probed by perf/exp_precision_probe.py.

    Returns ``[batch, seq, heads, head_dim]`` attention output in q's dtype.
    """
    mask, st = _prepare("flash_mha", q, k, mask, block_q, block_k, interpret,
                        causal, precision, window)
    b, s_q, n, d = q.shape
    out = _flash(_fold(q), _fold(k), _fold(v), mask, st)
    return out.reshape(b, n, s_q, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# latent attention: a second score term whose key the heads share
# ---------------------------------------------------------------------------
#
#   s_h[i, j] = (q_h[i] . k_h[j] + q_rope_h[i] . k_rope[j]) * scale,  j <= i
#   o_h = softmax_j(s_h) v_h
#
# with q, k ``d`` wide, the rope pair ``d_rope`` wide, v ``d_v`` wide and
# ``k_rope`` held once a position for ``group`` heads (all of them in a
# deepseek_v3 block).  The score is two MXU products, d and d_rope deep,
# summed in f32 before the softmax: no operand is padded to a common width
# and k_rope is read by index map, never repeated in HBM.  The kernels are
# the three above, given the extra refs as ``rope``; the launchers differ
# in their block specs.  The dkv launcher walks, per k_rope head and K
# block, the Q blocks of each of its ``group`` heads in turn: dK and dV
# are written once a head, dk_rope once the whole group is summed.

_MLA_STATIC = ("scale", "causal", "tiles", "lane", "interpret", "precision",
               "group")


def _row_spec(lane, bq, at):
    """Block spec of a row statistic (``[bn, 1, s]`` lane-major, else
    ``[bn, s, 1]``) for a kernel whose grid step owns ``bq`` rows at
    ``at(*grid ids) -> (head, block)``."""
    if lane:
        return pl.BlockSpec((1, 1, bq), lambda *g: (at(*g)[0], 0, at(*g)[1]))
    return pl.BlockSpec((1, bq, 1), lambda *g: (*at(*g), 0))


def _row_operands(lane, *stats):
    return [x[:, None, :] if lane else x[:, :, None] for x in stats]


def _mla_specs(tiles: Tiles, causal, n_kv, group):
    """``(at_q(width), at_k(width, shared))``: block specs of a q-side and
    of a k-side operand for the grid (heads, q blocks, kv blocks); a
    ``shared`` operand is read at head ``b // group``."""
    bq, bk, _ = tiles
    k_block = _k_block_of(causal, bq, bk, n_kv)
    heads = {False: lambda b: b, True: _kv_head_of(group)}
    at_q = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bq, w), lambda b, i, j: (b, i, 0))
    at_k = lambda w, shared=False: pl.BlockSpec(  # noqa: E731
        (1, bk, w), lambda b, i, j: (heads[shared](b), k_block(i, j), 0))
    return at_q, at_k


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _mla_fwd(q, qr, k, kr, v, *, scale, causal, tiles: Tiles, lane: bool,
             interpret, precision=None, group=1):
    bn, s_q, d = q.shape
    d_r, d_v = qr.shape[-1], v.shape[-1]
    bq, bk, _ = tiles
    n_q, n_kv = s_q // bq, k.shape[1] // bk
    at_q, at_k = _mla_specs(tiles, causal, n_kv, group)

    def kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, *rest):
        _fwd_kernel(None, q_ref, k_ref, v_ref, *rest, scale=scale,
                    causal=causal, tiles=tiles, n_kv=n_kv, lane_lse=lane,
                    precision=precision, rope=(qr_ref, kr_ref))

    out, lse = pl.pallas_call(
        kernel,
        name="flash_mla_fwd",
        grid=(bn, n_q, n_kv),
        in_specs=[at_q(d), at_q(d_r), at_k(d), at_k(d_r, True), at_k(d_v)],
        out_specs=[at_q(d_v), _row_spec(lane, bq, lambda b, i, j: (b, i))],
        out_shape=[_sds(q, (bn, s_q, d_v), q.dtype),
                   _sds(q, (bn, 1, s_q) if lane else (bn, s_q, 1),
                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d_v), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=_compiler_params("fwd", tiles, max(d, d_v),
                                         q.dtype.itemsize, d_r),
        interpret=interpret,
    )(q, qr, k, kr, v)
    return out, (lse[:, 0, :] if lane else lse[:, :, 0])


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _mla_bwd_dq(q, qr, k, kr, v, do, lse, delta, *, scale, causal,
                tiles: Tiles, lane: bool, interpret, precision=None, group=1):
    """dq, dq_rope: grid (heads, q blocks, kv blocks)."""
    bn, s_q, d = q.shape
    d_r, d_v = qr.shape[-1], v.shape[-1]
    bq, bk, _ = tiles
    n_q, n_kv = s_q // bq, k.shape[1] // bk
    at_q, at_k = _mla_specs(tiles, causal, n_kv, group)
    row_spec = _row_spec(lane, bq, lambda b, i, j: (b, i))

    def kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dqr_ref, dq_acc, dqr_acc):
        _bwd_dq_kernel(None, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_acc, scale=scale, causal=causal,
                       tiles=tiles, n_kv=n_kv, lane_lse=lane,
                       precision=precision,
                       rope=(qr_ref, kr_ref, dqr_ref, dqr_acc))

    return pl.pallas_call(
        kernel,
        name="flash_mla_bwd_dq",
        grid=(bn, n_q, n_kv),
        in_specs=[at_q(d), at_q(d_r), at_k(d), at_k(d_r, True), at_k(d_v),
                  at_q(d_v), row_spec, row_spec],
        out_specs=[at_q(d), at_q(d_r)],
        out_shape=[_sds(q, q.shape, q.dtype), _sds(q, qr.shape, qr.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, d_r), jnp.float32)],
        compiler_params=_compiler_params("dq", tiles, max(d, d_v),
                                         q.dtype.itemsize, d_r),
        interpret=interpret,
    )(q, qr, k, kr, v, do, *_row_operands(lane, lse, delta))


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _mla_bwd_dkv(q, qr, k, kr, v, do, lse, delta, *, scale, causal,
                 tiles: Tiles, lane: bool, interpret, precision=None,
                 group=1):
    """dk, dv, dk_rope: grid (k_rope heads, kv blocks, q blocks of each
    head of the group in turn)."""
    bn, s_q, d = q.shape
    d_r, d_v = qr.shape[-1], v.shape[-1]
    bq, bk, sub = tiles
    n_q, n_kv = s_q // bq, k.shape[1] // bk
    q_block = _q_block_of(causal, bq, bk, n_q)
    head = lambda b, i: b * group + _div(i, n_q)  # noqa: E731
    q_at = lambda b, j, i: (  # noqa: E731
        head(b, i), q_block(j, jax.lax.rem(i, jnp.int32(n_q))))
    at_q = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bq, w), lambda b, j, i: (*q_at(b, j, i), 0))
    own = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bk, w), lambda b, j, i: (head(b, i), j, 0))
    shared = pl.BlockSpec((1, bk, d_r), lambda b, j, i: (b, j, 0))
    if lane:    # [bn, s_q / sub, 1, sub]: see _flash_bwd_dkv
        row_spec = pl.BlockSpec((1, bq // sub, 1, sub),
                                lambda b, j, i: (*q_at(b, j, i), 0, 0))
        rows = [x.reshape(bn, s_q // sub, 1, sub) for x in (lse, delta)]
    else:
        row_spec = _row_spec(False, bq, q_at)
        rows = _row_operands(False, lse, delta)

    def kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, do_ref, lse_ref,
               delta_ref, dk_ref, dv_ref, dkr_ref, dk_acc, dv_acc, dkr_acc):
        _bwd_dkv_kernel(None, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                        scale=scale, causal=causal, tiles=tiles, n_q=n_q,
                        lane_lse=lane, precision=precision, group=group,
                        rope=(qr_ref, kr_ref, dkr_ref, dkr_acc))

    return pl.pallas_call(
        kernel,
        name="flash_mla_bwd_dkv",
        grid=(kr.shape[0], n_kv, group * n_q),
        in_specs=[at_q(d), at_q(d_r), own(d), shared, own(d_v), at_q(d_v),
                  row_spec, row_spec],
        out_specs=[own(d), own(d_v), shared],
        out_shape=[_sds(q, k.shape, k.dtype), _sds(q, v.shape, v.dtype),
                   _sds(q, kr.shape, kr.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d_v), jnp.float32),
                        pltpu.VMEM((bk, d_r), jnp.float32)],
        compiler_params=_compiler_params("dkv", tiles, max(d, d_v),
                                         q.dtype.itemsize, d_r),
        interpret=interpret,
    )(q, qr, k, kr, v, do, *rows)


def _mla_common(q, qr, st: _Static) -> dict:
    """What the three launchers take alike; the scale is over the whole
    score width."""
    return dict(scale=(q.shape[-1] + qr.shape[-1]) ** -0.5, causal=st.causal,
                lane=_lse_lane_major(), interpret=st.interpret,
                precision=st.precision, group=st.group)


def _mla_fwd_res(q, qr, k, kr, v, st: _Static):
    return _mla_fwd(q, qr, k, kr, v, tiles=st.tiling[0],
                    **_mla_common(q, qr, st))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mla(q, qr, k, kr, v, st):
    return _mla_fwd_res(q, qr, k, kr, v, st)[0]


def _mla_vjp_fwd(q, qr, k, kr, v, st):
    out, lse = _mla_fwd_res(q, qr, k, kr, v, st)
    return out, (q, qr, k, kr, v, out, lse)


def _mla_vjp_bwd(st: _Static, res, do):
    q, qr, k, kr, v, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    common = _mla_common(q, qr, st)
    dq, dqr = _mla_bwd_dq(q, qr, k, kr, v, do, lse, delta,
                          tiles=st.tiling[1], **common)
    dk, dv, dkr = _mla_bwd_dkv(q, qr, k, kr, v, do, lse, delta,
                               tiles=st.tiling[2], **common)
    return dq, dqr, dk, dkr, dv


_mla.defvjp(_mla_vjp_fwd, _mla_vjp_bwd)


def mla_supported(q, q_rope, k, k_rope, v, block_q: int | None = None,
                  block_k: int | None = None) -> bool:
    """True when :func:`flash_mla` takes these shapes (else the caller
    runs the XLA composition, tpuframe.ops.attention)."""
    if any(x.ndim != 4 for x in (q, q_rope, k, k_rope, v)):
        return False
    b, s_q, n, d = q.shape
    s_kv, d_r, d_v = k.shape[1], q_rope.shape[-1], v.shape[-1]
    if (k.shape != (b, s_kv, n, d) or v.shape[:3] != (b, s_kv, n)
            or q_rope.shape != (b, s_q, n, d_r)
            or k_rope.shape[:2] != (b, s_kv) or k_rope.shape[3] != d_r
            or n % k_rope.shape[2]):
        return False
    if max(d, d_v) > 256 or d_r > _LANES or s_q % 8 or s_kv % 8:
        return False
    return _tiles_whole(_tiling(s_q, s_kv, max(d, d_v), q.dtype.itemsize,
                                block_q, block_k, d_r), s_q, s_kv)


def flash_mla(q: jax.Array, q_rope: jax.Array, k: jax.Array,
              k_rope: jax.Array, v: jax.Array, *, causal: bool = True,
              block_q: int | None = None, block_k: int | None = None,
              interpret: bool | None = None, precision=None) -> jax.Array:
    """Flash attention whose scores have two terms and whose values have a
    width of their own (the latent attention of a ``deepseek_v3`` block).

    Args:
      q, k: ``[batch, seq, heads, d]``, each head's own (the "nope" part).
      q_rope: ``[batch, seq, heads, d_rope]``.
      k_rope: ``[batch, seq_kv, rope_heads, d_rope]``; ``rope_heads``
        divides ``heads`` (1: one rotary key a position for all heads) and
        head ``h`` reads ``k_rope`` head ``h // (heads / rope_heads)`` where
        it lies; its gradient sums the group inside the dkv kernel.
      v: ``[batch, seq_kv, heads, d_v]``.
      causal, block_q, block_k, interpret, precision: as :func:`flash_mha`.

    Scores are ``(q.k + q_rope.k_rope) / sqrt(d + d_rope)``.  Returns
    ``[batch, seq, heads, d_v]`` in q's dtype.
    """
    if not mla_supported(q, q_rope, k, k_rope, v, block_q, block_k):
        raise ValueError(
            f"flash_mla: shapes q={q.shape} q_rope={q_rope.shape} "
            f"k={k.shape} k_rope={k_rope.shape} v={v.shape} do not tile; use "
            f"tpuframe.ops.attention.multihead_attention for the fallback")
    b, s_q, n, d = q.shape
    s_kv, n_r = k.shape[1], k_rope.shape[2]
    d_r, d_v = q_rope.shape[-1], v.shape[-1]
    tiling = _tiling(s_q, s_kv, max(d, d_v), q.dtype.itemsize, block_q,
                     block_k, d_r)
    def grid(name, t):   # dkv walks each rope-key head's group of heads
        n_q, n_k = s_q // t.block_q, s_kv // t.block_k
        return (f"{b * n_r}x{n_k}x{n // n_r * n_q}" if name == "dkv"
                else f"{b * n}x{n_q}x{n_k}")

    said = "; ".join(
        f"{name} q{t.block_q} k{t.block_k} sub{t.sub} grid {grid(name, t)}"
        for name, t in zip(_KERNELS, tiling))
    said += (f"; score products {d} + {d_r} deep, value products {d_v} wide, "
             f"k_rope [{b}, {s_kv}, {n_r}, {d_r}] read by {n // n_r} heads")
    interpret = kernel_impl.resolve_interpret("flash_mla_attention",
                                              interpret, detail=said)
    st = _Static(causal, tiling, interpret, precision, None, n // n_r)
    out = _mla(_fold(q), _fold(q_rope), _fold(k), _fold(k_rope), _fold(v),
               st)
    return out.reshape(b, n, s_q, d_v).transpose(0, 2, 1, 3)
