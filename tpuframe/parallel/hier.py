"""Slice-aware two-level gradient collectives (the DCN-crushing lowering).

PERF §23 priced the pod-scale cost structure: on the composed
``dp=2,fsdp=2;slices=2`` spec 21% of the wire bytes ride the ~32x
slower DCN fabric and account for 87% of modeled comm time.  The
MLPerf-pods recipe (*Scale MLPerf-0.6 models on Google TPU-v3 Pods*,
arXiv:1909.09756) attacks exactly that term by restructuring the flat
cross-slice gradient mean into three fabric-matched phases:

  reduce-scatter(mean) over the in-slice axes      [ICI, full bytes]
  all-reduce(mean) over the slice axis on the      [DCN, 1/n_inner of
      1/n_inner shard                               the bytes]
  all-gather over the in-slice axes                [ICI, full bytes]

Only the middle leg crosses the data-center network, and it carries
``1/n_inner`` of the payload — the DCN byte column drops by the
in-slice world size.

Numerically the two-level mean equals the flat mean up to float
reassociation: the in-slice reduce-scatter divides by ``n_inner``, the
cross-slice mean by ``n_slice``, so every element is the sum over all
``N`` replicas divided by ``N`` — the golden-loss tests pin hier ==
flat to tight tolerance.

Like every other gradient-path modifier, the lowering is resolved per
program (env ``TPUFRAME_HIER`` > generation-gated tune DB, family
``hier_collectives`` > flat) and this module is a *seam*: the TF124
lint keeps collectives that name the ``slice`` axis out of every other
module, so cross-slice traffic is always the two-level shape (or a
signed exception).
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from tpuframe.parallel import collectives
from tpuframe.parallel import mesh as mesh_lib

AxisName = str | Sequence[str]
PyTree = Any

MODES = ("flat", "hier")
ENV_VAR = "TPUFRAME_HIER"
#: tune-DB family ``tune sweep --hier`` persists winners under.
DB_FAMILY = "hier_collectives"

SLICE_AXIS = mesh_lib.SLICE_AXIS

# Leaves smaller than this keep the flat cross-slice mean: the two-level
# shape triples a sub-KiB bias's collective count for no byte win, and the
# hier budgets' floors are sized to ignore the flat strays.
MIN_TWO_LEVEL_ELEMS = 1024


# ---------------------------------------------------------------------------
# Mode selection: env > tuning DB > default (the modifier chain idiom).
# ---------------------------------------------------------------------------


def validate_mode(mode: str) -> str:
    mode = (mode or "flat").strip().lower()
    if mode not in MODES:
        raise ValueError(f"unknown hierarchical-collective mode {mode!r}; "
                         f"expected one of {MODES} ({ENV_VAR})")
    return mode


def mode_from_env(env=os.environ) -> str | None:
    """The explicit ``TPUFRAME_HIER`` override, or None."""
    raw = env.get(ENV_VAR, "").strip()
    return validate_mode(raw) if raw else None


def resolve(program: str | None = None, family: str | None = None,
            default: str = "flat") -> tuple:
    """``(mode, source)`` for a step program: env override > tuning-DB
    winner (generation-gated; family ``hier_collectives`` persisted by
    ``python -m tpuframe.tune sweep --hier``) > ``default``.  ``source``
    is ``env``/``tune_db``/``default``."""
    env_val = mode_from_env()
    if env_val is not None:
        return env_val, "env"
    if program or family:
        from tpuframe.tune import db as tune_db

        db_val = tune_db.resolve_hier(program or "", family=family)
        if db_val is not None:
            try:
                return validate_mode(str(db_val)), "tune_db"
            except ValueError:
                pass  # a stale DB row must never break a run
    return validate_mode(default), "default"


# ---------------------------------------------------------------------------
# The two-level mean.
# ---------------------------------------------------------------------------


def split_axes(axes: AxisName) -> tuple[tuple[str, ...], bool]:
    """``(inner_axes, has_slice)`` — the bound reduction axes with the
    slice axis factored out.  ``has_slice`` False means the mesh is
    single-slice and the two-level lowering degenerates to flat."""
    bound = collectives._bound_axes(axes)
    inner = tuple(a for a in bound if a != SLICE_AXIS)
    return inner, SLICE_AXIS in bound


def hier_mean(tree: PyTree, axes: AxisName) -> PyTree:
    """Two-level cross-replica gradient mean over ``axes``.

    Per leaf: pad to a multiple of the in-slice world, reduce-scatter
    (mean) over the ICI axes, mean the 1/n_inner shard over the slice
    axis, all-gather the shard back over ICI, unpad.  Leaves under
    ``MIN_TWO_LEVEL_ELEMS`` (and any reduction whose inner world is 1)
    fall back to a flat mean — for a sub-floor leaf the two-level shape
    triples the collective count for no byte win, and with
    ``n_inner == 1`` every byte crosses DCN regardless.

    The result is invariant over all bound axes, matching
    ``average_gradients``' contract."""
    inner, has_slice = split_axes(axes)
    if not has_slice:
        # Single-slice mesh: nothing crosses DCN, flat is the lowering.
        return collectives.average_gradients(tree, axis=inner)

    def _hmean(g):
        vma = jax.typeof(g).vma
        varying_inner = tuple(a for a in inner if a in vma)
        sized = collectives._sized_axes(varying_inner)
        n_inner = lax.axis_size(sized)
        if n_inner == 1 or g.size < MIN_TWO_LEVEL_ELEMS:
            out = lax.pmean(g, SLICE_AXIS)
            if varying_inner:
                out = lax.pmean(out, varying_inner)
            else:
                out = collectives._clear_unit_axes(out, inner)
            return out.astype(g.dtype)
        flat = g.astype(jnp.float32).reshape(-1)
        pad = (-flat.size) % n_inner
        if pad:
            flat = jnp.pad(flat, (0, pad))
        flat = collectives._vary_over(flat, sized)
        # ICI: in-slice reduce-scatter(mean) — divides by n_inner.
        shard = collectives.reduce_scatter(flat, sized, average=True)
        # DCN: mean the 1/n_inner shard across slices — divides by
        # n_slice, completing the /N of the flat mean.
        shard = lax.pmean(shard, SLICE_AXIS)
        # ICI: gather the meaned shard back; tiled concat inverts the
        # scatter's contiguous chunk ownership exactly.
        full = collectives.allgather_invariant(shard, sized)
        out = full[:g.size].reshape(g.shape)
        out = collectives._clear_unit_axes(out, (*inner, SLICE_AXIS))
        return out.astype(g.dtype)

    return jax.tree.map(_hmean, tree)


# ---------------------------------------------------------------------------
# Fused (bucketed) two-level mean — the fusion_threshold compose.
# ---------------------------------------------------------------------------


def fused_hier_mean(tree: PyTree, axes: AxisName, *,
                    threshold_bytes: int) -> PyTree:
    """Two-level mean with Horovod-style fusion buckets: leaves pack into
    ≤``threshold_bytes`` same-kind buffers (``fusion._bucketize``'s exact
    buckets) and each buffer takes ONE three-phase lowering — rs(mean)
    over ICI, cross-slice mean of the 1/n_inner shard over DCN, ag back —
    so the collective count drops from 3·n_leaves to 3·n_buckets at the
    same wire bytes.  ``threshold_bytes <= 0`` → one lowering per leaf.
    Degenerates to ``fusion.staged_pmean`` on a single-slice mesh."""
    from tpuframe.parallel import fusion

    inner, has_slice = split_axes(axes)
    if not has_slice:
        return fusion.staged_pmean(tree, axes,
                                   threshold_bytes=threshold_bytes)
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    if threshold_bytes <= 0:
        buckets = [[i] for i in range(len(leaves))]
    else:
        buckets = fusion._bucketize(leaves, threshold_bytes)
    out: list = [None] * len(leaves)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            out[i] = hier_mean(leaves[i], axes)
            continue
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        red = hier_mean(flat, axes)
        off = 0
        for i in bucket:
            sz = leaves[i].size
            out[i] = red[off:off + sz].reshape(leaves[i].shape)
            off += sz
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# ZeRO-1 seam: two-stage scatter/gather primitives.  They live HERE, not
# in zero1.py, so TF124 holds — every collective naming the slice axis
# stays at this seam.
# ---------------------------------------------------------------------------


def linear_index(inner_axes: tuple[str, ...]):
    """Chunk index member (slice ``s``, inner ``j``) owns under the
    two-stage scatter: ``j * n_slice + s`` — inner-major, because the
    in-slice scatter runs first and the cross-slice scatter subdivides
    each in-slice chunk.  :func:`gather` inverts in slice-then-inner
    order so the same index recovers the same rows."""
    return collectives._linear_index((*tuple(inner_axes), SLICE_AXIS))


def scatter_mean(flat: jax.Array,
                 inner_axes: tuple[str, ...]) -> jax.Array:
    """Two-stage reduce-scatter(mean) of a flat operand padded to a
    multiple of the FULL world ``n_inner * n_slice``: in-slice rs(mean)
    over ICI (divides by n_inner, full bytes on the fast fabric), then
    cross-slice rs(mean) of the 1/n_inner chunk over DCN.  Member (s, j)
    receives chunk
    ``linear_index(inner_axes)`` of the n chunks — zero1's dynamic-slice
    index math works unchanged with that index."""
    chunk = collectives.reduce_scatter(flat, inner_axes, average=True)
    return collectives.reduce_scatter(chunk, SLICE_AXIS, average=True)


def gather(shard: jax.Array, inner_axes: tuple[str, ...]) -> jax.Array:
    """Inverse of :func:`scatter_mean`'s ownership: all-gather over the
    slice axis FIRST (DCN, 1/n_inner of the bytes, reassembling each
    in-slice chunk), then over the inner axes (ICI, full bytes)."""
    chunk = collectives.allgather_invariant(shard, SLICE_AXIS)
    return collectives.allgather_invariant(chunk, inner_axes)


# ---------------------------------------------------------------------------
# Gate self-check: seeded flat-vs-hier positives against the ICI/DCN
# split, numeric hier == flat, and the TF124 seam self-lint.
# ---------------------------------------------------------------------------

# The anti-pattern this module exists to remove: one flat all-reduce
# whose single group spans both slices of an 8-device slice=2 mesh.
# comm_split must charge its FULL bytes to DCN — if it reads as ICI the
# gate is blind to the very term the lowering crushes.
_SEEDED_FLAT_HLO = """\
HloModule seeded_hier_flat_cross_slice

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[65536]) -> f32[65536] {
  %p0 = f32[65536]{0} parameter(0)
  ROOT %ar = f32[65536]{0} all-reduce(f32[65536]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
}
"""

# Its two-level twin: in-slice reduce-scatter ({0..3},{4..7} — iota
# [2,4]<=[8]), cross-slice all-reduce on the 1/4 shard ({0,4},{1,5},
# {2,6},{3,7} — strided iota), in-slice all-gather back.  Only the
# shard-sized middle leg may land in the DCN column.
_SEEDED_HIER_HLO = """\
HloModule seeded_hier_two_level

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[65536]) -> f32[65536] {
  %p0 = f32[65536]{0} parameter(0)
  %rs = f32[16384]{0} reduce-scatter(f32[65536]{0} %p0), replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%add
  %ar = f32[16384]{0} all-reduce(f32[16384]{0} %rs), replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add
  ROOT %ag = f32[65536]{0} all-gather(f32[16384]{0} %ar), replica_groups=[2,4]<=[8], dimensions={0}
}
"""

_SEEDED_MESH = {"slice": 2, "data": 4}
_SEEDED_N_DEVICES = 8


def _seeded_split_problems() -> list:
    from tpuframe.analysis import collective_graph as cg
    from tpuframe.analysis import shardflow

    problems = []
    flat = shardflow.comm_split(cg.parse_graph(_SEEDED_FLAT_HLO), None,
                                mesh_shape=_SEEDED_MESH,
                                n_devices=_SEEDED_N_DEVICES)
    hier = shardflow.comm_split(cg.parse_graph(_SEEDED_HIER_HLO), None,
                                mesh_shape=_SEEDED_MESH,
                                n_devices=_SEEDED_N_DEVICES)
    if flat["dcn_bytes"] != 65536 * 4:
        problems.append(
            f"hier seeded positive: the flat cross-slice all-reduce "
            f"charged {flat['dcn_bytes']} bytes to DCN, expected "
            f"{65536 * 4} — comm_split is blind to the flat anti-pattern")
    if hier["dcn_bytes"] != 16384 * 4:
        problems.append(
            f"hier seeded twin: the two-level lowering charged "
            f"{hier['dcn_bytes']} bytes to DCN, expected {16384 * 4} "
            f"(the 1/n_inner shard) — the split mis-attributes a level")
    # Census ruler: a collective is priced at its RESULT bytes when no
    # hlo_audit report is supplied — the rs row is shard-sized, the ag
    # row full-sized.
    if hier["ici_bytes"] != (16384 + 65536) * 4:
        problems.append(
            f"hier seeded twin: the in-slice scatter+gather charged "
            f"{hier['ici_bytes']} bytes to ICI, expected "
            f"{(16384 + 65536) * 4}")
    if not problems and flat["dcn_bytes"] != 4 * hier["dcn_bytes"]:
        problems.append(
            f"hier seeded pair: DCN ratio flat/hier is "
            f"{flat['dcn_bytes']}/{hier['dcn_bytes']}, expected the "
            f"n_inner=4 reduction")
    return problems


def _numeric_problems() -> list:
    """hier_mean == flat pmean on the real multi-device backend (the
    fusion gate's psum-linearity idiom).  Skips quietly below 4 devices
    — the analysis child always runs with 8."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if jax.device_count() < 4 or jax.device_count() % 2:
        return []
    n = jax.device_count()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n // 2, slices=2))
    axes = mesh_lib.batch_axes(mesh)
    x = np.linspace(-2.0, 2.0, n * 2048, dtype=np.float32).reshape(n, 2048)

    def _flat(v):
        return jax.tree.map(lambda g: lax.pmean(g, axes), v)

    def _hier(v):
        return hier_mean(v, axes)

    spec = P(axes)
    problems = []
    try:
        want = jax.jit(jax.shard_map(_flat, mesh=mesh, in_specs=spec,
                                     out_specs=spec))(x)
        got = jax.jit(jax.shard_map(_hier, mesh=mesh, in_specs=spec,
                                    out_specs=spec))(x)
    except Exception as e:  # noqa: BLE001 — report, don't crash CI
        return [f"hier numeric check failed to run: "
                f"{type(e).__name__}: {e}"]
    err = float(np.max(np.abs(np.asarray(want) - np.asarray(got))))
    if err > 1e-6:
        problems.append(
            f"hier numeric check: two-level mean deviates from the flat "
            f"mean by {err:.3e} (> 1e-6) on the {n}-device slice=2 mesh")
    return problems


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
    # 2. seeded flat/two-level pair against the ICI/DCN split
    problems += _seeded_split_problems()
    # 3. the two-level mean is numerically the flat mean
    problems += _numeric_problems()
    # 4. TF124 self-lint: cross-slice collectives stay at this seam
    from tpuframe.analysis.source_lint import lint_paths, lint_source

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for f in lint_paths([pkg_root]):
        if f.rule == "TF124":
            problems.append(f"self-lint: {f}")
    # 5. seeded positive: the rule itself is alive (a known-bad snippet
    # outside the seam MUST fire, and the suppression MUST silence it) —
    # without this, a refactor that breaks the rule reads as a clean tree.
    bad = 'def f(g):\n    return lax.pmean(g, ("data", "slice"))\n'
    if not any(f.rule == "TF124"
               for f in lint_source(bad, path="parallel/step.py")):
        problems.append("TF124 seeded positive did not fire: a raw "
                        "cross-slice lax.pmean outside parallel/hier.py "
                        "went unflagged")
    ok = ('def f(g):\n    return lax.pmean(g, ("data", "slice"))'
          '  # tf-lint: ok[TF124]\n')
    if any(f.rule == "TF124"
           for f in lint_source(ok, path="parallel/step.py")):
        problems.append("TF124 suppression comment (# tf-lint: "
                        "ok[TF124]) did not silence the seeded positive")
    return problems
