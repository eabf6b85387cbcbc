"""Mixture-of-experts routing — top-k gating with capacity (Switch/GShard
formulation), built for expert parallelism over the ``expert`` mesh axis.

Not a reference capability (SURVEY.md §3c: no MoE workload); included
because expert parallelism is a first-class mesh axis in this framework.
The dispatch/combine are dense einsums over a one-hot token→(expert, slot)
tensor — static shapes, MXU-friendly, and under auto-SPMD with the expert
dim of the weights sharded over ``expert``, GSPMD lowers the dispatch
einsum to the same all-to-all a hand-written MoE runtime performs.

All routing math runs in float32 regardless of activation dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route_topk(gate_logits: jax.Array, *, k: int, capacity: int):
    """Top-k token→expert assignment with per-expert capacity.

    gate_logits: ``[T, E]`` (f32 recommended).
    Returns ``(dispatch [T, E, C] f32 0/1, combine [T, E, C] f32,
    aux_loss scalar)``.  Tokens overflowing an expert's capacity are
    dropped for that expert (their combine weights are 0 — the residual
    connection carries them, standard Switch behavior).
    """
    t, e = gate_logits.shape
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)  # [T, E]

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    masked_gates = gates
    prior_count = jnp.zeros((e,), jnp.float32)   # slots used per expert
    chosen_masks = []
    chosen_weights = []

    for _ in range(k):
        choice = jnp.argmax(masked_gates, axis=-1)              # [T]
        mask = jax.nn.one_hot(choice, e, dtype=jnp.float32)     # [T, E]
        # position of each token in its chosen expert's queue
        pos_in_expert = (jnp.cumsum(mask, axis=0) - mask) + prior_count[None]
        keep = mask * (pos_in_expert < capacity)
        slot = jax.nn.one_hot((pos_in_expert * keep).astype(jnp.int32),
                              capacity, dtype=jnp.float32)      # [T, E, C]
        dispatch = dispatch + keep[..., None] * slot
        weight = jnp.sum(gates * keep, axis=-1, keepdims=True)  # [T, 1]
        combine = combine + (keep * weight)[..., None] * slot
        chosen_masks.append(mask)
        chosen_weights.append(weight)
        prior_count = prior_count + jnp.sum(keep, axis=0)
        masked_gates = masked_gates * (1.0 - mask)

    # Renormalize the k gate weights so kept tokens' weights sum to ~1.
    denom = sum(chosen_weights)
    denom = jnp.where(denom > 0, denom, 1.0)
    combine = combine / denom[..., None]

    # Load-balance aux loss (Switch): E * sum_e mean_gates_e * frac_routed_e,
    # computed on the FIRST choice (standard) before capacity dropping.
    me = jnp.mean(gates, axis=0)                   # [E]
    ce = jnp.mean(chosen_masks[0], axis=0)         # [E]
    aux = e * jnp.sum(me * ce)
    return dispatch, combine, aux


def capacity_for(tokens: int, num_experts: int, k: int,
                 capacity_factor: float) -> int:
    """Static per-expert capacity: ceil(k*T/E * factor), min 1, multiple of
    4 to keep the slot dim tile-friendly."""
    raw = int(tokens * k / num_experts * capacity_factor) + 1
    return max(4, (raw + 3) // 4 * 4)


# ---------------------------------------------------------------------------
# Dropless routing over the experts held here
# ---------------------------------------------------------------------------
#
# The second expert layer (``models/afmoe.py``).  Every token picks ``k`` of
# ``num_experts``; this chip holds ``held`` of them, experts ``[first, first
# + held)``, and computes exactly the rows picked for those: sorted by
# expert into a buffer whose every ``TILE_ROWS`` rows belong to one expert,
# multiplied by that expert's weights in one grouped matrix product a
# projection (the Mosaic kernels ``moe_gmm``, ``moe_gmm_dx``, ``moe_gmm_dw``
# below), weighted and summed back per token.  Rows picked for experts held
# elsewhere are neither gathered nor computed, and no row is dropped: the
# buffer holds ``capacity_factor`` times the balanced load, and a batch that
# overflows it takes an exact loop over the held experts instead
# (``lax.cond``), slower and never wrong.

import functools  # noqa: E402
import math  # noqa: E402
from typing import NamedTuple  # noqa: E402

from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from tpuframe.ops import kernel_impl  # noqa: E402
from tpuframe.ops.fused_xent import _vary_like  # noqa: E402

TILE_ROWS = 128     # buffer rows a grid step owns: one MXU pass of rows
_BLOCK_COLS = 1024  # columns of an expert's weight a grid step holds
_DW_COLS = 512      # likewise for the weight gradient's f32 accumulator


def route_sigmoid_topk(logits: jax.Array, bias: jax.Array, *, k: int,
                       scale: float = 1.0, normalize: bool = True):
    """Sigmoid scores, top-k by score plus a selection bias.

    logits ``[T, E]``; bias ``[E]`` selects and does not weigh, and takes
    no gradient.  Returns ``(idx [T, k] int32, weights [T, k] f32)`` with
    ``weights = scale * s_e / (sum of the k picked s + 1e-20)``."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)), k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), picked * scale


def expert_load(idx: jax.Array, num_experts: int) -> jax.Array:
    """How many picks each of ``num_experts`` got: ``[E]`` f32."""
    hit = idx.reshape(-1, 1) == jnp.arange(num_experts)[None, :]
    return jnp.sum(hit, axis=0, dtype=jnp.float32)


class Plan(NamedTuple):
    """Where every pick for a held expert lies in the row buffer.

    The buffer has ``n_tiles`` tiles of ``tile`` rows; expert ``e``'s rows
    fill ``max(1, ceil(count_e / tile))`` consecutive tiles (its last tile
    padded with rows that weigh nothing), the experts in order; the last
    tile is never used, so its rows read zero and stand for "not here"."""
    tok: jax.Array          # [R] token of each buffer row (0 where padding)
    pick: jax.Array         # [R] flat pick t * k + j of each buffer row
    valid: jax.Array        # [R] bool: the row is a pick, not padding
    pos: jax.Array          # [T, k] buffer row of each pick; R - 1: not here
    tile_expert: jax.Array  # [n_tiles] held expert of each tile
    tiles_used: jax.Array   # [1] tiles in use, at most n_tiles - 1 if it fits
    counts: jax.Array       # [held] picks for each held expert
    fits: jax.Array         # [] bool: every pick for a held expert has a row


def make_plan(idx: jax.Array, *, first: int, held: int, n_tiles: int,
              tile: int = TILE_ROWS) -> Plan:
    t, k = idx.shape
    p, rows = t * k, n_tiles * tile
    le = idx.reshape(p) - first
    local = jnp.logical_and(le >= 0, le < held)
    le = jnp.where(local, le, held)
    hit = le[:, None] == jnp.arange(held)[None, :]            # [P, held]
    csum = jnp.cumsum(hit.astype(jnp.int32), axis=0)
    counts = csum[-1]
    rank = jnp.sum(jnp.where(hit, csum, 0), axis=1) - 1       # -1: not here
    tiles_e = jnp.maximum((counts + tile - 1) // tile, 1)
    tile_end = jnp.cumsum(tiles_e)
    tiles_used = tile_end[-1]
    seg_start = (tile_end - tiles_e) * tile
    row = seg_start[jnp.minimum(le, held - 1)] + rank
    here = jnp.logical_and(local, row < rows - tile)
    pos = jnp.where(here, row, rows - 1).reshape(t, k)

    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        held - 1).astype(jnp.int32)
    order = jnp.argsort(le, stable=True)      # by expert, then by token
    e_row = jnp.repeat(tile_expert, tile)
    j = jnp.arange(rows)
    r = j - seg_start[e_row]
    valid = jnp.logical_and(r < counts[e_row], j // tile < tiles_used)
    pick = order[jnp.clip(counts.cumsum()[e_row] - counts[e_row] + r,
                          0, p - 1)]
    pick = jnp.where(valid, pick, 0).astype(jnp.int32)
    return Plan(tok=pick // k, pick=pick, valid=valid,
                pos=pos.astype(jnp.int32), tile_expert=tile_expert,
                tiles_used=tiles_used.reshape(1).astype(jnp.int32),
                counts=counts, fits=tiles_used <= n_tiles - 1)


# -- the grouped matrix product ---------------------------------------------


def _cols(n: int, cap: int) -> int:
    """Largest block of ``n`` columns that is a multiple of 128 and at most
    ``cap``; the whole of ``n`` where there is none."""
    for m in range(min(cap, n) // 128, 0, -1):
        if n % (m * 128) == 0:
            return m * 128
    return n


def _gmm_kernel(te_ref, used_ref, x_ref, w_ref, o_ref, *, rhs_dim):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (rhs_dim,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(pl.program_id(1) >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_dw_kernel(te_ref, used_ref, x_ref, g_ref, o_ref, acc_ref, *,
                   n_tiles):
    i = pl.program_id(1)
    e = te_ref[i]

    @pl.when(i < used_ref[0])
    def _():
        @pl.when(jnp.logical_or(i == 0, te_ref[jnp.maximum(i - 1, 0)] != e))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(jnp.logical_or(
            i == used_ref[0] - 1,
            te_ref[jnp.minimum(i + 1, n_tiles - 1)] != e))
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _live(i, used):
    """The tile a grid step past the tiles in use re-reads: the last one in
    use, which the pipeline holds already."""
    return jnp.minimum(i, used[0] - 1)


@functools.partial(jax.jit, static_argnames=("transpose", "tile",
                                             "interpret"))
def _gmm_call(x, w, tile_expert, tiles_used, *, transpose: bool, tile: int,
              interpret: bool):
    """``out[rows of tile i] = x[rows of tile i] @ w[tile_expert[i]]`` (or
    ``@ w[...].T``) for the tiles in use, zero for the others."""
    rows, kdim = x.shape
    n_tiles = rows // tile
    held, wk, wn = w.shape
    n_out = wk if transpose else wn
    bn = _cols(n_out, _BLOCK_COLS)
    if transpose:
        w_spec = pl.BlockSpec(
            (1, bn, wn), lambda j, i, te, u: (te[_live(i, u)], j, 0))
    else:
        w_spec = pl.BlockSpec(
            (1, wk, bn), lambda j, i, te, u: (te[_live(i, u)], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, rhs_dim=1 if transpose else 0),
        name="moe_gmm_dx" if transpose else "moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_out // bn, n_tiles),
            in_specs=[pl.BlockSpec((tile, kdim),
                                   lambda j, i, te, u: (_live(i, u), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tile, bn), lambda j, i, te, u: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, n_out), x.dtype,
                                       vma=jax.typeof(x).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert, tiles_used, x, w)


@functools.partial(jax.jit, static_argnames=("held", "tile", "interpret"))
def _gmm_dw_call(x, g, tile_expert, tiles_used, *, held: int, tile: int,
                 interpret: bool):
    """``dw[e] = sum over e's tiles of x_tile.T @ g_tile``: ``[held, K, N]``
    in ``x``'s dtype, accumulated in float32 (every expert has a tile)."""
    rows, kdim = x.shape
    n_tiles = rows // tile
    n_out = g.shape[1]
    bn = _cols(n_out, _DW_COLS)
    return pl.pallas_call(
        functools.partial(_gmm_dw_kernel, n_tiles=n_tiles),
        name="moe_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_out // bn, n_tiles),
            in_specs=[pl.BlockSpec((tile, kdim),
                                   lambda j, i, te, u: (_live(i, u), 0)),
                      pl.BlockSpec((tile, bn),
                                   lambda j, i, te, u: (_live(i, u), j))],
            out_specs=pl.BlockSpec(
                (1, kdim, bn), lambda j, i, te, u: (te[_live(i, u)], 0, j)),
            scratch_shapes=[pltpu.VMEM((kdim, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((held, kdim, n_out), x.dtype,
                                       vma=jax.typeof(x).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert, tiles_used, x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gmm(x, w, tile_expert, tiles_used, tile, interpret):
    """Grouped matrix product: buffer rows ``x [R, K]``, each tile of
    ``tile`` rows times its expert's ``w [held, K, N]`` -> ``[R, N]``."""
    return _gmm_call(x, w, tile_expert, tiles_used, transpose=False,
                     tile=tile, interpret=interpret)


def _gmm_fwd(x, w, tile_expert, tiles_used, tile, interpret):
    out = _gmm_call(x, w, tile_expert, tiles_used, transpose=False,
                    tile=tile, interpret=interpret)
    return out, (x, w, tile_expert, tiles_used)


def _gmm_bwd(tile, interpret, res, g):
    x, w, tile_expert, tiles_used = res
    dx = _gmm_call(g, w, tile_expert, tiles_used, transpose=True, tile=tile,
                   interpret=interpret)
    dw = _gmm_dw_call(x, g, tile_expert, tiles_used, held=w.shape[0],
                      tile=tile, interpret=interpret)
    return dx, dw.astype(w.dtype), None, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _gmm_xla(x, w, tile_expert, tiles_used, *, tile: int):
    """What the kernels compute, as XLA composes it: each tile times its
    expert's gathered weight.  For backends without Mosaic."""
    n_tiles = x.shape[0] // tile
    out = jnp.einsum("tmk,tkn->tmn", x.reshape(n_tiles, tile, -1),
                     w[tile_expert])
    live = jnp.arange(n_tiles) < tiles_used[0]
    return jnp.where(live[:, None, None], out, 0).reshape(x.shape[0], -1)


# -- rows in, rows out ------------------------------------------------------


@jax.custom_vjp
def _dispatch(x, tok, pos):
    """``xs[r] = x[tok[r]]``; backward a gather too: ``dx[t]`` sums the
    rows of the token's picks."""
    return x[tok]


def _dispatch_fwd(x, tok, pos):
    return x[tok], pos


def _dispatch_bwd(pos, dxs):
    dx = dxs[pos[:, 0]].astype(jnp.float32)
    for j in range(1, pos.shape[1]):
        dx = dx + dxs[pos[:, j]]
    return dx.astype(dxs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, plan: Plan):
    """``y[t] = sum_j w[t, j] * ys[pos[t, j]]``: a gather per pick, never a
    scatter (a pick not held here reads the zero row)."""
    y = w[:, 0, None] * ys[plan.pos[:, 0]]
    for j in range(1, w.shape[1]):
        y = y + w[:, j, None] * ys[plan.pos[:, j]]
    return y.astype(ys.dtype)


def _combine_fwd(ys, w, plan):
    return _combine(ys, w, plan), (ys, w, plan)


def _combine_bwd(res, dy):
    ys, w, plan = res
    w_row = jnp.where(plan.valid, w.reshape(-1)[plan.pick], 0.0)
    dys = (w_row[:, None] * dy[plan.tok]).astype(ys.dtype)
    dyf = dy.astype(jnp.float32)
    dw = jnp.stack([jnp.sum(dyf * ys[plan.pos[:, j]], axis=-1)
                    for j in range(w.shape[1])], axis=1)
    return dys, dw.astype(w.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _swiglu(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _loop_over_experts(x, idx, w, w_in, w_out, first):
    """The exact stand-in for a batch that overflows the row buffer: every
    held expert over every token, weighted by what the token gave it."""
    def one(y, ew):
        e, wi, wo = ew
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        out = jnp.dot(_swiglu(jnp.dot(x, wi)), wo)
        return y + we[:, None].astype(x.dtype) * out, None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                    (jnp.arange(w_in.shape[0]), w_in, w_out))
    return y


def buffer_tiles(tokens: int, k: int, held: int, num_experts: int,
                 capacity_factor: float, tile: int = TILE_ROWS):
    """``(n_tiles, always_fits)`` of the row buffer: room for
    ``capacity_factor`` times the balanced load of the held experts, never
    more than the worst case (every token's picks all held here), a padded
    tile an expert, and the zero tile."""
    worst = tokens * min(k, held)
    room = min(worst, math.ceil(capacity_factor * tokens * k * held
                                / num_experts))
    return -(-room // tile) + held + 1, room >= worst


def routed_experts(x: jax.Array, idx: jax.Array, w: jax.Array,
                   gate: jax.Array, up: jax.Array, down: jax.Array, *,
                   first: int, num_experts: int,
                   capacity_factor: float = 2.0, tile: int = TILE_ROWS,
                   interpret: bool | None = None):
    """``sum over the picks held here of w * SwiGLU_e(x)``.

    x ``[T, H]``; idx, w ``[T, k]`` from :func:`route_sigmoid_topk` over
    all ``num_experts``; gate, up ``[held, H, I]`` and down ``[held, I,
    H]`` are experts ``[first, first + held)``, already in ``x``'s dtype.
    Returns ``(y [T, H], plan)``; ``plan.counts`` and ``plan.fits`` are
    what the counters read."""
    t, k = idx.shape
    held = gate.shape[0]
    n_tiles, always_fits = buffer_tiles(t, k, held, num_experts,
                                        capacity_factor, tile)
    said = (f"{held} experts of {num_experts}, {n_tiles} tiles of {tile} "
            f"rows for {t} tokens x {k}, [{x.shape[1]} -> {gate.shape[2]}]")
    why = kernel_impl.no_mosaic() if interpret is None else None
    if why is not None:
        # no Mosaic here: the einsum over each tile's gathered weights
        kernel_impl.record("moe_gmm", "xla", f"{why}; {said}")
        product = functools.partial(_gmm_xla, tile=tile)
    else:
        interpret = kernel_impl.resolve_interpret("moe_gmm", interpret, said)
        product = lambda x, w, te, used: gmm(  # noqa: E731
            x, w, te, used, tile, interpret)
    with jax.named_scope("moe.dispatch"):
        plan = make_plan(idx, first=first, held=held, n_tiles=n_tiles,
                         tile=tile)
    # the kernels' backward hands each operand a cotangent of its own
    # type: inside shard_map the replicated weights vary as the rows do
    # (the cast's transpose is the psum their gradient needs)
    w_in, down = (_vary_like(m, x)
                  for m in (jnp.concatenate([gate, up], axis=-1), down))

    def sorted_rows(x, w, w_in, down):
        with jax.named_scope("moe.dispatch"):
            xs = _dispatch(x, plan.tok, plan.pos)
        with jax.named_scope("moe.experts"):
            h = product(xs, w_in, plan.tile_expert, plan.tiles_used)
            ys = product(_swiglu(h), down, plan.tile_expert,
                         plan.tiles_used)
        with jax.named_scope("moe.combine"):
            return _combine(ys, w, plan)

    if always_fits:
        return sorted_rows(x, w, w_in, down), plan
    y = lax.cond(plan.fits, sorted_rows,
                 lambda x, w, w_in, down: _loop_over_experts(
                     x, idx, w, w_in, down, first),
                 x, w, w_in, down)
    return y, plan
