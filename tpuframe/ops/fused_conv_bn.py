"""Fused 1x1-conv + BatchNorm backward — the byte-floor pallas kernel.

Why this op exists (PERF.md §6.3/§7.4b): the ResNet-50 train step moves
143.5 GB/step on-chip (offline AOT census 149.0 GB, 4% apart), ~105 GB
of it in the backward pass, and the census showed the traffic is
STRUCTURAL — layouts are fine, folded-BN is a null, remat is negative.
The one remaining lever is TOUCH COUNT: XLA's backward for a conv+BN
pair materializes the BN input-cotangent ``g`` (activation-sized) in HBM
and re-reads it twice (conv data-grad, conv weight-grad):

    XLA:   pass1 reads (x, dy)          -> BN sums
           pass2 reads (x, dy) writes g -> BN input grad
           dgrad reads (g)              -> da
           wgrad reads (g, a)           -> dW
           = 9 activation-sized touches

    here:  pass1 reads (x, dy)          -> BN sums  (XLA, fuses to one pass)
           pass2 reads (a, x, dy) writes da; g lives only in VMEM
           = 6 activation-sized touches

Every 1x1 conv in a ResNet-50 bottleneck (conv1, conv3, downsample — the
large-C tensors) is a matmul over ``(N*H*W, Cin) x (Cin, Cout)``, so
"conv backward" here is two MXU dots per tile fed by a ``g`` computed on
the fly from the folded per-channel BN-backward coefficients

    g = s*dy - u*x + c,   s = gamma*r,  u = gamma*r^2*c2,
                          c = gamma*r^2*c2*mu - gamma*r*c1,
    c1 = mean(dy), c2 = mean(dy * xhat), r = rsqrt(var+eps)

(the exact training-mode BN backward, differentiating through the batch
statistics).

LAYOUT CONTRACT (the round-5 lesson, measured): XLA:TPU lays ResNet
conv activations out as ``{3,0,2,1}`` — physically C on the 128 lanes,
N on the 8 sublanes, spatial dims major.  A naive ``reshape(N*H*W, C)``
before a pallas call demands a different physical order, and the
re-layout copies it forces cost MORE than the fusion saves (measured
136.3 vs 81.4 GB at b=256 for the first cut of this kernel).  So:

  * the FORWARD is a plain ``lax.conv_general_dilated`` + folded BN —
    byte-identical ops to the unfused model, conv layouts end to end;
  * the BACKWARD kernel consumes ``[H*W, N, C]`` views, whose default
    (descending) layout is physically IDENTICAL to ``{3,0,2,1}`` on
    ``[N,H,W,C]`` — the transpose+reshape at the boundary is a bitcast,
    not a copy, and rows of the matmul are just a permutation of
    ``N*H*W`` (BN sums, dW and da are row-order-invariant).

Removing g's write + two reads is 3 activation-sized touches per fused
pair; verified offline by ``perf/exp_hlo_offline.py BN=fused`` (the AOT
cost model counts a pallas call as operands+outputs, which for this
streaming kernel is the honest count).

The 3x3 convs and the stem keep the XLA path: their g tensors are the
small-C minority of the bytes and an implicit-GEMM halo kernel is not
worth the risk for them (measured priority, not principle).

KNOWN EXCLUSION — ResNet-50 layer4 downsample: the VMEM gate in
``_pick_tiles`` keeps the resident weight block + f32 dW accumulator
under the 10 MB budget via ``k * c * 6 <= _VMEM_BUDGET``; the layer4
downsample 1x1 is K=1024 -> C=2048, i.e. 1024*2048*6 = 12.58 MB, so
``supported()`` returns False and that one pair falls back to the
plain-XLA composition (correct, just unfused).  Every other ResNet-50
1x1 fits.  Tracked as the first entry of
``tpuframe.analysis.budgets.KNOWN_VMEM_EXCLUSIONS`` — the analysis CI
gate cross-checks the registry against this gate so the exclusion list
cannot silently drift from the code (PERF.md §11).

Reference parity: the reference's ResNet comes from torchvision
(SURVEY.md §3a); its conv+BN backward is cuDNN's fused
``cudnnBatchNormalizationBackwardEx`` + conv grad kernels.  This is the
TPU-native equivalent of that fusion, not a translation of it.

CPU tests run the kernel under the pallas interpreter
(tests/test_fused_conv_bn.py): value + gradient parity vs the unfused
composition, f32 tight / bf16 tolerance, stride-2, module parity vs
``nn.Conv + nn.BatchNorm``, and golden-loss equivalence of the full
ResNet-50 step.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuframe.ops import kernel_impl

# Row budget per grid step (spatial-tile x batch-tile rows): 2048 rows of
# up-to-2048-wide bf16 activations keeps the worst ResNet-50 1x1 shape
# near ~10 MB of VMEM including the f32 dW accumulator (see _pick_tiles).
DEFAULT_BLOCK_ROWS = 2048
_VMEM_BUDGET = 10 * 1024 * 1024


def supported(h: int, w: int, n: int, k: int, c: int,
              block_rows: int = DEFAULT_BLOCK_ROWS) -> bool:
    """True when the backward kernel's static tiling fits (else callers
    keep the plain-XLA composition).  ``h``/``w`` spatial dims (after any
    stride slicing), ``n`` = batch, ``k``/``c`` = in/out channels."""
    return _pick_tiles(h, w, n, k, c, block_rows) is not None


def _pick_tiles(h: int, w: int, n: int, k: int, c: int,
                block_rows: int) -> tuple[int] | None:
    """(tn,): batch-tile size.  Each grid step processes one spatial row
    of the [H, W, N, C] view — W*tn matmul rows — so tn shrinks (by
    halving, must divide N) until the row budget and VMEM fit."""
    if k > 4096 or c > 4096 or k * c * 6 > _VMEM_BUDGET:  # W bf16 + acc f32
        return None
    tn = n
    while tn > 1 and (w * tn > block_rows
                      or _vmem_est(w * tn, k, c) > _VMEM_BUDGET):
        tn //= 2
    if n % tn != 0 or w * tn > block_rows \
            or _vmem_est(w * tn, k, c) > _VMEM_BUDGET:
        return None
    return (tn,)


def _vmem_est(rows: int, k: int, c: int) -> int:
    # Mosaic DOUBLE-BUFFERS every grid-blocked operand/result (a, x, dy,
    # da — the 2x factor; the real v5e compiler OOM'd at 16 MB VMEM when
    # this estimate ignored that), plus the f32 g temp on the kernel
    # stack, the resident W block and the f32 dW accumulator scratch.
    dbuf = 2 * (2 * (rows * k * 2) + 2 * (rows * c * 2))
    return dbuf + rows * c * 4 + k * c * 2 + k * c * 4


# ---------------------------------------------------------------------------
# backward pass 2: the fused kernel
# ---------------------------------------------------------------------------


def _bwd_kernel(a_ref, w_ref, x_ref, dy_ref, coef_ref,
                da_ref, dw_ref, dw_acc,
                *, n_h: int, n_n: int, precision=None):
    """Grid is (H, N/tn), sequential (dW carries).  coef rows: 0=s, 1=u,
    2=c (f32).  Blocks are [1, W, tn, channels] — one spatial row of the
    [H, W, N, C] view per step; the collapse to [W*tn, channels] rows is
    a sublane-group stack, not a re-layout.  g = s*dy - u*x + c is
    computed in f32 in VMEM, used by both dots, and never written back;
    dW accumulates in f32 scratch and is emitted once at the last step.
    """
    hi = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(jnp.logical_and(hi == 0, ni == 0))
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    _, w_sp, tn, k = a_ref.shape
    c = x_ref.shape[-1]
    rows = w_sp * tn
    s = coef_ref[0, :][None, :]                       # [1, C] f32
    u = coef_ref[1, :][None, :]
    cc = coef_ref[2, :][None, :]
    a = a_ref[...].reshape(rows, k)
    x = x_ref[...].reshape(rows, c).astype(jnp.float32)
    dy = dy_ref[...].reshape(rows, c).astype(jnp.float32)
    g = (s * dy - u * x + cc).astype(w_ref.dtype)     # VMEM only

    da_ref[...] = jax.lax.dot_general(                # g @ W^T   [rows, K]
        g, w_ref[...], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32
    ).astype(da_ref.dtype).reshape(1, w_sp, tn, k)
    dw_acc[...] += jax.lax.dot_general(               # a^T @ g   [K, C]
        a, g, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(hi == n_h - 1, ni == n_n - 1))
    def _emit():
        dw_ref[...] = dw_acc[...]


def _sds(like: jax.Array, shape, dtype) -> jax.ShapeDtypeStruct:
    """Inherit varying-mesh-axes so the op composes with shard_map (same
    rationale as flash_attention._sds)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fused_bwd_matmuls(a4t, w_c, x4t, dy4t, coef, *, block_rows, interpret,
                       precision=None):
    """da4t, dW given [H, W, N, C]-view operands + folded coefficients."""
    h, w_sp, n, k = a4t.shape
    c = x4t.shape[-1]
    tiles = _pick_tiles(h, w_sp, n, k, c, block_rows)
    assert tiles is not None, "caller must gate on supported()"
    (tn,) = tiles
    n_n = n // tn

    da4t, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, n_h=h, n_n=n_n,
                          precision=precision),
        grid=(h, n_n),
        in_specs=[
            pl.BlockSpec((1, w_sp, tn, k), lambda i, j: (i, 0, j, 0)),  # a
            pl.BlockSpec((k, c), lambda i, j: (0, 0)),                  # W
            pl.BlockSpec((1, w_sp, tn, c), lambda i, j: (i, 0, j, 0)),  # x
            pl.BlockSpec((1, w_sp, tn, c), lambda i, j: (i, 0, j, 0)),  # dy
            pl.BlockSpec((3, c), lambda i, j: (0, 0)),                  # coef
        ],
        out_specs=[
            pl.BlockSpec((1, w_sp, tn, k), lambda i, j: (i, 0, j, 0)),  # da
            pl.BlockSpec((k, c), lambda i, j: (0, 0)),           # dW (last)
        ],
        out_shape=[
            _sds(a4t, (h, w_sp, n, k), a4t.dtype),
            _sds(a4t, (k, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((k, c), jnp.float32)],
        # dW carries across every step: both grid dims are sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(a4t, w_c, x4t, dy4t, coef)
    return da4t, dw


def _to_hwnc(x4):
    """[N, H, W, C] -> [H, W, N, C].  The default (descending) layout on
    the result is minor-to-major (C, N, W, H) — physically IDENTICAL to
    the conv layout {3,0,2,1} on the input, so layout assignment folds
    this pure transpose into a bitcast (a transpose+reshape chain did
    NOT fold — measured 97.6 vs 81.4 GB baseline; this is the fix)."""
    return x4.transpose(1, 2, 0, 3)


def _from_hwnc(x4t):
    """[H, W, N, C] -> [N, H, W, C] (inverse, same bitcast argument)."""
    return x4t.transpose(2, 0, 1, 3)


# ---------------------------------------------------------------------------
# the custom-vjp core: y, mean, var = conv1x1 + train-mode BN (NHWC)
# ---------------------------------------------------------------------------


def _conv1x1(a4, w2, precision=None):
    """1x1 stride-1 conv via conv_general_dilated — the SAME op (same
    dtype contract: bf16 in/out, f32 MXU accumulation internally) the
    unfused flax model runs, so XLA's layout assignment sees nothing
    new.  No preferred_element_type: its f32 output would poison the
    VJP's conv dtypes, and flax.nn.Conv doesn't use it either."""
    return lax.conv_general_dilated(
        a4, w2[None, None], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def conv1x1_bn_train(cfg: tuple, a4: jax.Array, w: jax.Array,
                     gamma: jax.Array, beta: jax.Array):
    """``cfg = (eps, block_rows, interpret)`` (hashable statics).

    a4: [N, H, W, K] activations, w: [K, C] f32 params, gamma/beta: [C]
    f32.  Returns (y [N,H,W,C] in a4.dtype, mean [C] f32, var [C] f32 —
    biased, flax-style).  The mean/var outputs exist for the
    running-stats update and are NOT differentiated through (callers
    must stop_gradient them, as FusedConvBN does; their cotangents are
    ignored in the backward, matching flax's treatment of running
    statistics).
    """
    y, mean, var, _ = _fwd_math(cfg, a4, w, gamma, beta)
    return y, mean, var


def _fwd_math(cfg, a4, w, gamma, beta):
    eps, _, _ = cfg
    x = _conv1x1(a4, w.astype(a4.dtype))
    # f32 accumulation without f32 materialization (folded_bn.py
    # rationale: the convert feeds the reduce, only C-sized f32 lands).
    axes = (0, 1, 2)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(xf), axis=axes)
                      - jnp.square(mean), 0.0)
    r = lax.rsqrt(var + eps)
    aa = gamma.astype(jnp.float32) * r
    bb = beta.astype(jnp.float32) - mean * aa
    y = x * aa.astype(x.dtype) + bb.astype(x.dtype)
    return y, mean, var, x


def _core_fwd(cfg, a4, w, gamma, beta):
    y, mean, var, x = _fwd_math(cfg, a4, w, gamma, beta)
    return (y, mean, var), (a4, w, x, mean, var, gamma)


def _core_bwd(cfg, res, cots):
    eps, block_rows, interpret = cfg
    a4, w, x, mean, var, gamma = res
    dy, _dmean, _dvar = cots          # stats cotangents: see docstring
    n, h, w_sp, c = x.shape
    m = n * h * w_sp

    # Pass 1 (XLA): both BN reductions in one fused pass over (x, dy),
    # native layout — reductions are layout-agnostic.
    r = lax.rsqrt(var + eps)
    dyf = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean) * r
    sum_dy = jnp.sum(dyf, axis=(0, 1, 2))
    sum_dyxhat = jnp.sum(dyf * xhat, axis=(0, 1, 2))
    dgamma = sum_dyxhat
    dbeta = sum_dy

    # Folded per-channel coefficients for g = s*dy - u*x + c.
    gf = gamma.astype(jnp.float32)
    c1 = sum_dy / m
    c2 = sum_dyxhat / m
    s = gf * r
    u = gf * r * r * c2
    cc = u * mean - s * c1
    coef = jnp.stack([s, u, cc])                    # [3, C] f32

    # Pass 2 (pallas) on [H, W, N, C] views — bitcasts on the conv layout.
    da4t, dw = _fused_bwd_matmuls(
        _to_hwnc(a4), w.astype(a4.dtype), _to_hwnc(x), _to_hwnc(dy), coef,
        block_rows=block_rows, interpret=interpret)
    da4 = _from_hwnc(da4t)
    # w is stored f32 and cast to compute dtype inside the fwd; the f32
    # accumulator already IS the gradient through that cast.
    return da4, dw.astype(w.dtype), dgamma.astype(gamma.dtype), \
        dbeta.astype(gamma.dtype)


conv1x1_bn_train.defvjp(_core_fwd, _core_bwd)


def conv1x1_bn_reference(a4, w, gamma, beta, *, eps):
    """The unfused composition (1x1 conv -> flax-semantics train BN) the
    kernel is parity-tested against; differentiable end to end by XLA.
    Delegates to the SAME forward math as the custom_vjp (the module's
    fallback-path contract is bit-identical forward numerics)."""
    y, mean, var, _ = _fwd_math((eps, 0, False), a4, w, gamma, beta)
    return y, mean, var


# ---------------------------------------------------------------------------
# flax module: drop-in for a Conv(1x1, no bias) -> BatchNorm pair
# ---------------------------------------------------------------------------

import flax.linen as nn  # noqa: E402  (after-jax import, flax convention)


class FusedConvBN(nn.Module):
    """1x1 conv (no bias) + BatchNorm with the fused pallas backward.

    Parameter layout: ``kernel`` keeps nn.Conv's ``(1, 1, K, C)`` shape so
    torchvision-style weight ports map unchanged; ``scale``/``bias`` and
    the ``batch_stats`` ``mean``/``var`` entries match nn.BatchNorm, so
    the harness's cross-replica batch-stats averaging (parallel/step.py)
    applies unmodified.  (Flax auto-naming still re-keys module names vs
    the unfused pair — same caveat as the ``bn="folded"`` toggle.)

    Strides are handled OUTSIDE the fused core: a strided 1x1 conv is
    exactly a spatial slice followed by the stride-1 conv, and the
    slice's VJP (zero-scatter) stays with XLA.
    """

    features: int
    strides: int = 1
    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    scale_init: nn.initializers.Initializer = nn.initializers.ones
    kernel_init: nn.initializers.Initializer = \
        nn.initializers.variance_scaling(2.0, "fan_out", "normal")
    block_rows: int = DEFAULT_BLOCK_ROWS
    interpret: bool | None = None     # None = auto (CPU -> interpreter)

    @nn.compact
    def __call__(self, x):
        k_in = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init,
                            (1, 1, k_in, self.features), self.param_dtype)
        scale = self.param("scale", self.scale_init, (self.features,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((self.features,),
                                                  jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((self.features,),
                                                jnp.float32))

        x = x.astype(self.dtype)
        if self.strides > 1:
            x = x[:, ::self.strides, ::self.strides, :]
        b, h, w_sp, _ = x.shape
        w2d = kernel.reshape(k_in, self.features)

        if self.use_running_average:
            # Eval: conv + affine fold with running stats — plain XLA.
            mean, var = ra_mean.value, ra_var.value
            xx = _conv1x1(x, w2d.astype(self.dtype))
            r = lax.rsqrt(var + self.epsilon)
            aa = scale.astype(jnp.float32) * r
            bb = bias.astype(jnp.float32) - mean * aa
            y = xx * aa.astype(self.dtype) + bb.astype(self.dtype)
        else:
            fits = supported(h, w_sp, b, k_in, self.features,
                             self.block_rows)
            if fits and not self.is_initializing():
                interpret = kernel_impl.resolve_interpret(
                    "fused_conv_bn", self.interpret)
                cfg = (float(self.epsilon), int(self.block_rows),
                       bool(interpret))
                y, mean, var = conv1x1_bn_train(cfg, x, w2d, scale, bias)
            else:
                # Shape outside the kernel's tiling (or init pass): the
                # reference composition, identical numerics.
                if not fits:
                    kernel_impl.record(
                        "fused_conv_bn", "xla",
                        f"shape {(b, h, w_sp, k_in, self.features)} "
                        f"outside the kernel's tiling")
                y, mean, var = conv1x1_bn_reference(
                    x, w2d, scale, bias, eps=self.epsilon)
            if not self.is_initializing():
                mom = self.momentum
                ra_mean.value = mom * ra_mean.value + (1 - mom) * \
                    jax.lax.stop_gradient(mean)
                ra_var.value = mom * ra_var.value + (1 - mom) * \
                    jax.lax.stop_gradient(var)

        return y
