"""ZeRO-1 weight-update sharding for the plain data-parallel step.

The flagship DP configs all-reduce gradients and then run a fully
REPLICATED optimizer update: every chip stores the whole optimizer state
(2x param bytes for Adam moments) and applies the whole update — work and
memory that is identical on all n replicas.  *Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training* (arXiv:2004.13336,
PAPERS.md) gives the standard fix, ZeRO stage 1:

    all-reduce(grads); update(all params)          # replicated update
        ⇓
    g_i = reduce-scatter(grads)                    # same wire bytes
    p_i = update(param shard i, g_i)               # 1/n compute + state
    params = all-gather(p_i)                       # param bytes out

Same update math (the optimizer must be ELEMENT-WISE — sgd/momentum/
adam(w) qualify; anything coupling across elements of one leaf, e.g.
LARS' per-layer trust ratio or global-norm clipping folded into the
transform, is out of scope and documented so), same total wire traffic
class, but the optimizer state lives sharded — HBM residency drops by
(n-1)/n — and the update compute is 1/n per chip.  This is ROADMAP open
item 1 and the discipline arXiv:2011.03641 credits for DP scaling to pod
sizes.

Layout
------
Each parameter leaf is flattened to 1-D and zero-padded to a multiple of
the weight-update world size ``n`` (pad-to-multiple, so EVERY param tree
takes the sharded path, not just divisible ones — :func:`padding_census`
reports the waste, typically <<1%).  The optimizer state is built by
``tx.init`` over flat ``[padded]`` zero templates (element-wise
optimizers initialize moments to zeros, so this is exactly the replicated
init reshaped) and placed sharded over dim 0; it is NEVER materialized
replicated.  Inside the shard_map'd step each replica then holds:

  - params: the full replicated tree (unchanged — ZeRO-1 shards only the
    update, not the forward/backward);
  - opt_state: flat ``[padded/n]`` moment shards + replicated scalars;
  - grads: local per-replica gradients (the step builder arranges this).

:func:`sharded_update` runs reduce-scatter(mean) → per-shard ``tx.update``
→ ``optax.apply_updates`` → tiled all-gather, slicing each replica's
param shard with ``dynamic_slice`` at the same row-major linear index
``lax.psum_scatter(tiled=True)`` scatters to (so scatter, slice and
gather all agree on who owns which rows).  The gradient norm comes from
shard-local sums of squares + one scalar psum — the padding contributes
zeros, so it is bit-comparable to ``optax.global_norm`` of the averaged
global gradient.

Selection
---------
Per run via ``TPUFRAME_WEIGHT_UPDATE=zero1|replicated`` with the PR 3/5
resolution chain (:func:`resolve`): env > generation-gated tuning DB
(family ``weight_update_*``, searched offline by ``python -m
tpuframe.tune sweep --zero1``) > ``replicated`` default.  The analysis
gate proves the collective swap per build: the ``dp-zero1`` strategy's
HLO audit must show zero all-reduces above the scalar floor and
reduce-scatter + all-gather bytes exactly matching
:func:`tpuframe.analysis.budgets.zero1_budget`.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuframe.parallel import collectives
from tpuframe.parallel import mesh as mesh_lib

PyTree = Any

MODES = ("replicated", "zero1")
ENV_VAR = "TPUFRAME_WEIGHT_UPDATE"


# ---------------------------------------------------------------------------
# Mode selection: env > tuning DB > default (mem.policy.resolve's chain).
# ---------------------------------------------------------------------------


def validate_mode(mode: str) -> str:
    mode = (mode or "replicated").strip().lower()
    if mode not in MODES:
        raise ValueError(f"unknown weight-update mode {mode!r}; "
                         f"expected one of {MODES} ({ENV_VAR})")
    return mode


def mode_from_env(env=os.environ) -> str | None:
    """The explicit ``TPUFRAME_WEIGHT_UPDATE`` override, or None."""
    raw = env.get(ENV_VAR, "").strip()
    return validate_mode(raw) if raw else None


def resolve(program: str | None = None, family: str | None = None,
            default: str = "replicated") -> tuple:
    """``(mode, source)`` for a step program: env override > tuning-DB
    winner (generation-gated; family ``weight_update_*`` persisted by the
    offline sweep) > ``default``.  ``source`` is ``env``/``tune_db``/
    ``default`` — emitted in the ``weight_update`` run event so mode
    provenance is always on record."""
    env_val = mode_from_env()
    if env_val is not None:
        return env_val, "env"
    if program or family:
        from tpuframe.tune import db as tune_db

        db_val = tune_db.resolve_weight_update(program or "", family=family)
        if db_val is not None:
            try:
                return validate_mode(str(db_val)), "tune_db"
            except ValueError:
                pass  # a stale DB row must never break a run
    return validate_mode(default), "default"


# ---------------------------------------------------------------------------
# Pad-to-multiple layout helpers.
# ---------------------------------------------------------------------------


def _size(leaf) -> int:
    return int(np.prod(leaf.shape)) if leaf.shape else 1


def _padded(size: int, n: int) -> int:
    return -(-size // n) * n


def padded_len(size: int, n: int) -> int:
    """Public face of the pad-to-multiple layout: the flat length a
    ``size``-element leaf occupies when sharded ``n`` ways.

    This is also the elastic-resize contract (:mod:`tpuframe.elastic`):
    the pad region is zero at init (``tx.init`` over zero templates) and
    stays zero forever (``flat_pad`` pads grads with zeros; the mean of
    zeros reduce-scatters to zero; element-wise optimizers keep zero
    moments on zero grads), so resharding a flat moment vector n→n′ is
    EXACTLY truncate-or-zero-pad to ``padded_len(size, n')`` — no data
    beyond the true ``size`` ever carries state.  ``elastic.check()``
    cross-checks its own mirror of this arithmetic against this function
    so the two layouts can never drift apart."""
    return _padded(int(size), int(n))


def world_size(mesh: Mesh, axes=None) -> int:
    """Number of weight-update shards: the product of ``axes`` sizes.
    The default is the mesh's own data-parallel axes (slice-aware: on a
    hierarchical multi-slice mesh the DCN ``slice`` axis shards too)."""
    if axes is None:
        axes = mesh_lib.batch_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in axes if a in mesh.shape]))


def padded_bytes(params: PyTree, n: int) -> int:
    """Total bytes of the flat pad-to-``n`` layout — the exact operand
    bytes of the step's reduce-scatter AND result bytes of its all-gather
    (grads are cast to param dtype before the scatter)."""
    return int(sum(_padded(_size(p), n) * np.dtype(p.dtype).itemsize
                   for p in jax.tree.leaves(params)))


def padding_census(params: PyTree, n: int) -> dict:
    """Per-leaf padding accounting for the pad-to-multiple layout.

    Returned dict: ``leaves`` rows (name/shape/dtype/size/padded/
    pad_waste/padded_bytes) + totals and ``waste_frac``.  Committed with
    the sweep report so the documented-padding-census requirement is an
    artifact, not a claim."""
    rows = []
    total = padded_total = total_b = padded_b = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        size, padded = _size(leaf), _padded(_size(leaf), n)
        item = np.dtype(leaf.dtype).itemsize
        rows.append({
            "name": jax.tree_util.keystr(path),
            "shape": tuple(int(d) for d in leaf.shape),
            "dtype": str(np.dtype(leaf.dtype)),
            "size": size,
            "padded": padded,
            "pad_waste": padded - size,
            "padded_bytes": padded * item,
        })
        total += size
        padded_total += padded
        total_b += size * item
        padded_b += padded * item
    return {
        "n_shards": int(n),
        "leaves": rows,
        "total_elems": total,
        "padded_elems": padded_total,
        "total_bytes": total_b,
        "padded_bytes": padded_b,
        "waste_frac": (padded_total - total) / max(total, 1),
    }


# ---------------------------------------------------------------------------
# Sharded optimizer state: built in the flat [padded] layout, placed
# sharded, never materialized replicated.
# ---------------------------------------------------------------------------


def init_opt_state(tx: optax.GradientTransformation, params: PyTree,
                   n: int) -> PyTree:
    """``tx.init`` over flat ``[pad-to-n]`` zero templates of ``params``.

    Element-wise optimizers (sgd/momentum/adam(w)) initialize moments to
    zeros independent of param values, so this is the replicated init in
    the sharded layout — the exact-equivalence property the golden-loss
    tests pin.  ``params`` may be real arrays or ShapeDtypeStructs (for
    ``jax.eval_shape`` callers)."""
    return tx.init(jax.tree.map(
        lambda p: jnp.zeros((_padded(_size(p), n),), p.dtype), params))


def _is_opt_leaf_path(path) -> bool:
    head = path[0] if path else None
    return getattr(head, "name", None) == "opt_state"


def state_partition_specs(state, axes=mesh_lib.BATCH_AXES) -> PyTree:
    """Per-leaf PartitionSpec tree over a TrainState in ZeRO-1 layout:
    opt_state moment vectors shard dim 0 over ``axes``; everything else
    (params, step, rng, model_state, opt scalars) is replicated.  Built
    per-leaf because ``tx.init``'s tree structure is optimizer-dependent
    — the step builder calls this inside its jit trace."""
    axes = tuple(axes)

    def spec(path, leaf):
        if _is_opt_leaf_path(path) and getattr(leaf, "ndim", 0) >= 1:
            return P(axes)
        return P()

    return jax.tree_util.tree_map_with_path(spec, state)


def state_shardings(state, mesh: Mesh,
                    axes=mesh_lib.BATCH_AXES) -> PyTree:
    """NamedSharding twin of :func:`state_partition_specs`."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        state_partition_specs(state, axes))


def check_state_layout(state, n: int):
    """Trace-time guard: a replicated ``TrainState.create`` opt_state
    reaching the zero1 step would shard param-shaped moments down dim 0
    and fail later with an opaque shape error — catch it here instead."""
    sizes = {_padded(_size(p), n) for p in jax.tree.leaves(state.params)}
    for leaf in jax.tree.leaves(state.opt_state):
        if getattr(leaf, "ndim", 0) == 0:
            continue
        if leaf.ndim != 1 or _size(leaf) not in sizes:
            raise ValueError(
                f"opt_state leaf {tuple(leaf.shape)} is not in the ZeRO-1 "
                f"flat pad-to-{n} layout — build the state with "
                f"zero1.make_state (or init_opt_state), not "
                f"TrainState.create, when weight_update='zero1'")
    return state


def make_state(params: PyTree, tx: optax.GradientTransformation,
               mesh: Mesh | None = None, *, axes=None,
               model_state: PyTree | None = None,
               rng: jax.Array | None = None):
    """``TrainState.create`` twin for the zero1 path: the optimizer state
    is created directly in the sharded layout — with a mesh, a jitted
    init with sharded ``out_shardings`` so the ``[padded]`` moments are
    born distributed and no replicated copy ever exists; params/step/rng/
    model_state are placed replicated (ZeRO-1 keeps them so).  ``axes``
    defaults to the mesh's own data-parallel axes (slice-aware)."""
    from tpuframe.parallel import step as step_lib

    if axes is None:
        axes = mesh_lib.BATCH_AXES if mesh is None \
            else mesh_lib.batch_axes(mesh)
    n = world_size(mesh, axes) if mesh is not None else 1
    if mesh is None:
        opt = init_opt_state(tx, params, n)
    else:
        struct = jax.eval_shape(lambda: init_opt_state(tx, params, n))
        out_sh = jax.tree.map(
            lambda l: NamedSharding(
                mesh, P(tuple(axes)) if l.ndim >= 1 else P()), struct)
        opt = jax.jit(lambda: init_opt_state(tx, params, n),
                      out_shardings=out_sh)()
    state = step_lib.TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=opt,
        model_state={} if model_state is None else model_state,
        rng=jax.random.key(0) if rng is None else rng,
    )
    if mesh is None:
        return state
    repl = mesh_lib.replicated_sharding(mesh)

    def place(path, leaf):
        if _is_opt_leaf_path(path):
            return leaf  # already sharded by the jitted init
        return mesh_lib.host_device_put(leaf, repl)

    return jax.tree_util.tree_map_with_path(place, state)


# ---------------------------------------------------------------------------
# The sharded update itself (runs inside the shard_map'd step body).
# ---------------------------------------------------------------------------


def _psum_marked(x, bound: tuple[str, ...]):
    """psum over the axes ``x`` actually varies on."""
    ax = tuple(a for a in bound if a in jax.typeof(x).vma)
    return lax.psum(x, ax) if ax else x


def sharded_update(tx: optax.GradientTransformation, axes,
                   params: PyTree, opt_state: PyTree,
                   grads: PyTree, *,
                   fusion_threshold: int | None = None,
                   hier: bool = False,
                   ) -> tuple[PyTree, PyTree, jax.Array]:
    """reduce-scatter → 1/n optimizer update → all-gather.

    Called from the step tail with LOCAL per-replica gradients (the step
    builder keeps them unreduced on the zero1 path).  Returns
    ``(new_params, new_opt_state, grad_norm)``; ``opt_state`` is the
    per-replica shard view (``[padded/n]`` moments) and comes back in the
    same layout.  The reduce-scatter averages, so the update consumes the
    same global mean gradient as the replicated path.

    ``fusion_threshold`` buckets BOTH gradient-sized collectives Horovod-
    style (:mod:`tpuframe.parallel.fusion`): padded flat grads pack
    shard-aligned (``fusion.pack_for_scatter``) into ≤threshold-byte
    buffers, ONE reduce-scatter per bucket in, ONE all-gather per bucket
    out, every bucket's collective issued before any bucket is consumed.
    Wire bytes are EXACTLY the per-leaf path's pad-to-multiple totals
    (the zero1 budget holds unchanged); only the op count drops from
    n_leaves to n_buckets.

    ``hier=True`` on a multi-slice mesh (``axes`` includes the slice
    axis) swaps both gradient-sized collectives for their two-stage
    twins (:mod:`tpuframe.parallel.hier`, arXiv:1909.09756): the scatter
    runs in-slice over ICI first then cross-slice over DCN on the
    1/n_inner chunk, the gather inverts slice-first — so only 1/n_inner
    of the bytes touch the slow fabric, at the SAME total padded bytes.
    Chunk ownership becomes INNER-MAJOR (member (slice s, inner j) owns
    chunk ``j*n_slice + s``): the on-disk order of a sharded opt-state
    dump therefore permutes vs the flat lowering, but the flat
    ``[padded]`` global layout — what elastic resize and checkpoints
    address — is unchanged.  Single-slice (or ``n_inner == 1``) meshes
    degenerate to the flat lowering."""
    bound = collectives._bound_axes(axes)
    if not bound:
        # World of 1 (unmapped): the sharded path degenerates to the
        # replicated update on the flat layout's single shard.
        updates, new_opt = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_opt,
                optax.global_norm(grads))
    n = lax.axis_size(bound)

    from tpuframe.parallel import hier as hier_lib

    inner, has_slice = hier_lib.split_axes(bound)
    n_inner = lax.axis_size(inner)
    # Two-stage only when both levels are real; otherwise the flat
    # lowering IS the hierarchy (one level is trivial).
    two_stage = bool(hier) and has_slice and n_inner > 1
    idx = hier_lib.linear_index(inner) if two_stage \
        else collectives._linear_index(bound)

    def flat_pad(t):
        flat = t.reshape(-1)
        pad = _padded(flat.size, n) - flat.size
        return jnp.pad(flat, (0, pad)) if pad else flat

    # Grads in: ONE reduce-scatter per leaf (operand = padded grad bytes
    # — the wire cost the dp-zero1 CommBudget declares), averaging over
    # the world.  Zero padding reduces to zero.
    # With ``fusion_threshold`` the leaves pack into shard-aligned
    # buckets first — one scatter per bucket, all issued before any
    # shard is unpacked.
    def scatter(flat):
        if two_stage:
            return hier_lib.scatter_mean(flat, inner)
        return collectives.reduce_scatter(flat, bound, average=True)

    fused = fusion_threshold is not None
    if fused:
        from tpuframe.parallel import fusion

        g_leaves, g_def = jax.tree.flatten(grads)
        g_flat = [flat_pad(g) for g in g_leaves]
        buckets = fusion._bucketize(g_flat, fusion_threshold)
        issued = []
        for bucket in buckets:
            if len(bucket) == 1:
                issued.append(scatter(g_flat[bucket[0]]))
            else:
                issued.append(scatter(
                    fusion.pack_for_scatter([g_flat[i] for i in bucket],
                                            n)))
        g_out = [None] * len(g_leaves)
        for shard, bucket in zip(issued, buckets):
            if len(bucket) == 1:
                g_out[bucket[0]] = shard
                continue
            parts = fusion.split_scattered(
                shard, [g_flat[i].size // n for i in bucket])
            for i, part in zip(bucket, parts):
                g_out[i] = part
        gshard = jax.tree.unflatten(g_def, g_out)
    else:
        gshard = jax.tree.map(lambda g: scatter(flat_pad(g)), grads)
    # Params are replicated, so each replica's shard is a free local
    # slice at the same row-major linear index the scatter used.
    def param_shard(t):
        flat = flat_pad(t)
        chunk = flat.size // n
        return lax.dynamic_slice(flat, (idx * chunk,), (chunk,))

    pshard = jax.tree.map(param_shard, params)
    updates, new_opt = tx.update(gshard, opt_state, pshard)
    new_pshard = optax.apply_updates(pshard, updates)

    # ||mean grad||: shard-local sum of squares + one scalar psum (under
    # every audit floor).  Padding contributes exact zeros.
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(gshard))
    grad_norm = jnp.sqrt(_psum_marked(sq, bound))

    # Params out: tiled all-gather (result = padded param bytes), then
    # un-pad and fold back to the original shapes.
    def gather(shard):
        if two_stage:
            return hier_lib.gather(shard, inner)
        return collectives.allgather_invariant(shard, bound)

    def regather(shard, like):
        return gather(shard)[:_size(like)].reshape(like.shape)

    if fused:
        # Params out, bucketed: the same buckets the scatter used (grads
        # were cast to param dtype upstream, so kinds match), one
        # all-gather per bucket, every gather issued before any unpack.
        p_leaves = jax.tree.leaves(params)
        s_leaves, s_def = jax.tree.flatten(new_pshard)
        gathered = []
        for bucket in buckets:
            if len(bucket) == 1:
                gathered.append(gather(s_leaves[bucket[0]]))
            else:
                gathered.append(gather(
                    jnp.concatenate([s_leaves[i] for i in bucket])))
        p_out = [None] * len(p_leaves)
        for full, bucket in zip(gathered, buckets):
            if len(bucket) == 1:
                i = bucket[0]
                p_out[i] = full[:_size(p_leaves[i])].reshape(
                    p_leaves[i].shape)
                continue
            parts = fusion.split_gathered(
                full, n, [g_flat[i].size // n for i in bucket])
            for i, part in zip(bucket, parts):
                p_out[i] = part[:_size(p_leaves[i])].reshape(
                    p_leaves[i].shape)
        new_params = jax.tree.unflatten(s_def, p_out)
    else:
        new_params = jax.tree.map(regather, new_pshard, params)
    return new_params, new_opt, grad_norm


# ---------------------------------------------------------------------------
# Analysis-gate self-check.
# ---------------------------------------------------------------------------

# Files whose optimizer updates must route through the make_train_step /
# zero1 seam — TF110's scope, self-linted so the gate fails closed if a
# stray tx.update/apply_updates sneaks into harness or parallel code and
# silently bypasses the weight-update layout decision.
_TF110_SELF_LINT = (
    "parallel",
    "train.py",
)


def check() -> list:
    """Self-check for the ``python -m tpuframe.analysis`` CI gate.
    Returns problem strings; [] means healthy."""
    problems: list[str] = []
    # 1. the mode registry and env parsing agree
    for m in MODES:
        try:
            validate_mode(m)
        except Exception as e:  # noqa: BLE001 — report, don't crash CI
            problems.append(f"mode {m!r} failed validation: {e}")
    try:
        mode_from_env()
    except ValueError as e:
        problems.append(f"{ENV_VAR} is set to an invalid mode: {e}")
    # 2. pad-to-multiple layout arithmetic stays self-consistent
    probe = {"w": jax.ShapeDtypeStruct((3, 5), jnp.float32),
             "b": jax.ShapeDtypeStruct((7,), jnp.float32)}
    census = padding_census(probe, 8)
    if any(row["padded"] % 8 for row in census["leaves"]):
        problems.append("padding census produced a non-multiple shard")
    if census["padded_bytes"] != padded_bytes(probe, 8):
        problems.append("padding census / padded_bytes disagree")
    # 3. TF110 self-lint: optimizer updates stay at the seam
    from tpuframe.analysis.source_lint import lint_paths

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(pkg_root, p) for p in _TF110_SELF_LINT]
    for f in lint_paths([p for p in paths if os.path.exists(p)]):
        if f.rule == "TF110":
            problems.append(f"self-lint: {f}")
    return problems
