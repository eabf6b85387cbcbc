"""Explicit gradient-fusion buffers — the guaranteed HOROVOD_FUSION_THRESHOLD
mechanism (SURVEY.md §3b, tensor-fusion-buffer row).

Horovod packs many small gradient tensors into one 64–128 MB buffer per
cycle so each NCCL ring pays its latency once (key for the BERT workload's
~200 small tensors, SURVEY.md §1 config 4 [B:10]).  Under XLA the same role
is normally played by the compiler's all-reduce combiner, but that pass is
backend-internal: the GPU pipeline honors the DebugOptions threshold
(tpuframe.parallel.tuning maps the env knob onto it), the CPU pipeline does
not run it at all, and libtpu's combiner is tuned by private flags.  This
module therefore implements the fusion buffer *in the program itself*, where
it is visible, testable and backend-independent:

  grads are flattened leaf-by-leaf in deterministic tree order, greedily
  packed into same-dtype buckets of up to ``threshold_bytes``, each bucket
  concatenated into one 1-D buffer, ONE ``lax.psum`` issued per bucket, and
  the results split/reshaped back.

Two emission orders share that bucketing:

:func:`fused_psum` — the synchronous reference: pack → reduce → unpack one
  bucket at a time, in tree order.  Simple, and the identity the staged
  pass is tested against.

:func:`staged_psum` — the overlapped pass (the ``declared_overlapped``
  contract signer).  Every bucket's reduction is ISSUED before any bucket
  is consumed, and an ``optimization_barrier`` chain pins the program
  order so bucket k+1's packing + reduction sit between bucket k's
  reduction and its unpack.  On a backend that lowers collectives to
  async ``all-reduce-start``/``-done`` pairs, each completion window
  therefore contains the later buckets' collectives and packing compute
  — real windows for ``collective_graph.pair_async`` to see.  jax exposes
  no portable async psum form (probed via ``_HAS_ASYNC_PSUM``; no current
  release has one), so the start/done *split itself* is delegated to the
  backend scheduler: CPU XLA emits every all-reduce synchronous (PERF
  §21/§26 record this honestly), while async-capable pipelines get a
  program whose windows are provably non-empty.

``threshold_bytes <= 0`` disables packing (one collective per leaf — the
HOROVOD_FUSION_THRESHOLD=0 semantics).  The compiled-HLO effect is directly
assertable: the all-reduce op count drops from n_leaves to n_buckets
(tests/test_fusion.py).  Semantics are unchanged — psum is linear, so
psum(concat(gs)) == concat(psum(g) for g in gs) — which the golden-loss test
asserts against the implicit pmean-of-loss path.

The bucket-size knob resolves through the standard chain
(:func:`resolve`, mirroring ``zero1.resolve``):
``TPUFRAME_FUSION_THRESHOLD`` env > generation-gated ``tune_db.json``
winner (family ``fusion_threshold``, persisted by
``python -m tpuframe.tune sweep --fusion``) > default (off).
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any

ENV_VAR = "TPUFRAME_FUSION_THRESHOLD"

#: Bucket size the fused registry strategies pin (128 KiB): large enough
#: that the tiny audit models pack several leaves per bucket, small enough
#: that they emit MULTIPLE buckets — so every completion window has later
#: buckets' work legally interleavable (the nonzero-interior-window
#: property the schedule records pin).  Production thresholds come from
#: the sweep; Horovod's default is 64 MiB.
REGISTRY_THRESHOLD = 128 * 1024

# No jax release exposes an async psum (start/done split at the lax level);
# probed so the staged pass picks it up the release it appears instead of
# silently staying synchronous.
_HAS_ASYNC_PSUM = hasattr(lax, "psum_start") and hasattr(lax, "psum_done")

_HAS_BARRIER = hasattr(lax, "optimization_barrier")


def _leaf_kind(leaf) -> tuple:
    """Bucket compatibility key: dtype + vma (concat needs both to match)."""
    ty = jax.typeof(leaf)
    return (ty.dtype, tuple(sorted(ty.vma)))


def _bucketize(leaves: Sequence[jax.Array],
               threshold_bytes: int) -> list[list[int]]:
    """Greedy same-kind packing in leaf order; returns index buckets."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_kind = None
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize
        if cur and (_leaf_kind(leaf) != cur_kind
                    or cur_bytes + nbytes > threshold_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_kind = _leaf_kind(leaf)
    if cur:
        buckets.append(cur)
    return buckets


def bucket_census(leaves: Sequence, threshold_bytes: int) -> dict:
    """Deterministic bucketing accounting for a leaf list: per-bucket
    {leaves, bytes, kind} rows + totals.  Pure shape math (works on
    ShapeDtypeStructs) — what the sweep report and the self-check's
    arithmetic leg both consume, so the numbers in
    ``fusion_report_v5e_22.json`` are reproducible from shapes alone."""
    if threshold_bytes <= 0:
        buckets = [[i] for i in range(len(leaves))]
    else:
        buckets = _bucketize(leaves, threshold_bytes)
    rows = []
    for b in buckets:
        rows.append({
            "leaves": len(b),
            "bytes": int(sum(leaves[i].size * leaves[i].dtype.itemsize
                             for i in b)),
            "dtype": str(jnp.dtype(leaves[b[0]].dtype)),
        })
    return {
        "threshold_bytes": int(threshold_bytes),
        "n_leaves": len(leaves),
        "n_buckets": len(rows),
        "buckets": rows,
        "total_bytes": int(sum(r["bytes"] for r in rows)),
    }


def fused_psum(tree: PyTree, axes, *, threshold_bytes: int,
               mean: bool = False) -> PyTree:
    """Cross-replica sum (or mean) of every leaf with Horovod-style fusion.

    ``axes``: mesh axis name or tuple of names (as for ``lax.psum``); must be
    bound (inside ``shard_map``).  Leaves are packed into ≤``threshold_bytes``
    same-dtype buffers, one collective per buffer.  ``threshold_bytes <= 0``
    → one collective per leaf.  Synchronous emission order (pack → reduce →
    unpack per bucket) — the reference :func:`staged_psum` must match.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    denom = _mean_denom(axes) if mean else 1

    if threshold_bytes <= 0:
        out = [lax.psum(l, axes) for l in leaves]
    else:
        out = [None] * len(leaves)
        for bucket in _bucketize(leaves, threshold_bytes):
            if len(bucket) == 1:
                i = bucket[0]
                out[i] = lax.psum(leaves[i], axes)
                continue
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
            flat = lax.psum(flat, axes)
            off = 0
            for i in bucket:
                n = leaves[i].size
                out[i] = flat[off:off + n].reshape(leaves[i].shape)
                off += n
    if mean:
        out = [o / denom for o in out]
    return jax.tree.unflatten(treedef, out)


def fused_pmean(tree: PyTree, axes, *, threshold_bytes: int) -> PyTree:
    return fused_psum(tree, axes, threshold_bytes=threshold_bytes, mean=True)


def _mean_denom(axes) -> int:
    denom = 1
    for a in ((axes,) if isinstance(axes, str) else tuple(axes)):
        denom *= lax.axis_size(a)
    return denom


def staged_psum(tree: PyTree, axes, *, threshold_bytes: int,
                mean: bool = False) -> PyTree:
    """Overlapped bucketed reduction — same buckets and same math as
    :func:`fused_psum`, pipelined emission order.

    Issue stage: every bucket is packed and its reduction issued in tree
    order, nothing consumed.  Consume stage: bucket k is unpacked only
    after bucket k+1's reduction exists, pinned by an
    ``optimization_barrier`` chain (an op ``collective_graph`` chases
    through, so async pairing survives it).  On an async-capable backend
    each all-reduce's start→done window therefore contains the later
    buckets' packing + collectives; on sync-only CPU XLA the program is
    byte-identical traffic in a fixed order (PERF §26's measured caveat).
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    denom = _mean_denom(axes) if mean else 1
    if threshold_bytes <= 0:
        buckets = [[i] for i in range(len(leaves))]
    else:
        buckets = _bucketize(leaves, threshold_bytes)

    # Issue: pack + reduce every bucket before any unpack.  (When a lax
    # async psum form exists this is where the starts go; see
    # _HAS_ASYNC_PSUM above.)
    reduced = []
    for bucket in buckets:
        if len(bucket) == 1:
            flat = leaves[bucket[0]].reshape(-1)
        else:
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        reduced.append(lax.psum(flat, axes))

    # Consume: unpack bucket k strictly after bucket k+1's reduction.
    out = [None] * len(leaves)
    for b, bucket in enumerate(buckets):
        flat = reduced[b]
        if _HAS_BARRIER and b + 1 < len(buckets):
            flat, reduced[b + 1] = lax.optimization_barrier(
                (flat, reduced[b + 1]))
        if mean:
            flat = flat / denom
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = flat[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)


def staged_pmean(tree: PyTree, axes, *, threshold_bytes: int) -> PyTree:
    return staged_psum(tree, axes, threshold_bytes=threshold_bytes, mean=True)


# ---------------------------------------------------------------------------
# Shard-aligned packing for the zero1 (reduce-scatter/all-gather) seam.
# ---------------------------------------------------------------------------


def pack_for_scatter(flats: Sequence[jax.Array], n: int) -> jax.Array:
    """Pack already-padded flat leaves (each length a multiple of ``n``)
    so a reduce-scatter of the result hands every member the
    concatenation of its OWN per-leaf shards.

    A naive concat would give member k one contiguous [total/n] chunk
    that straddles leaf boundaries; reshaping each leaf to (n, len/n)
    and concatenating along axis 1 makes row k exactly concat(leaf
    shards k) — the layout zero1's per-leaf [padded/n] opt state needs.
    """
    return jnp.concatenate([f.reshape(n, -1) for f in flats],
                           axis=1).reshape(-1)


def split_scattered(shard: jax.Array,
                    chunk_sizes: Sequence[int]) -> list[jax.Array]:
    """Undo :func:`pack_for_scatter` on the scattered side: member k's
    [total/n] shard back into per-leaf [padded/n] shards."""
    out, off = [], 0
    for c in chunk_sizes:
        out.append(lax.dynamic_slice(shard, (off,), (int(c),)))
        off += int(c)
    return out


def split_gathered(full: jax.Array, n: int,
                   chunk_sizes: Sequence[int]) -> list[jax.Array]:
    """Undo :func:`pack_for_scatter` after an all-gather of the packed
    shards: the full [total] vector back into per-leaf [padded] flats."""
    rows = full.reshape(n, -1)
    out, off = [], 0
    for c in chunk_sizes:
        out.append(lax.dynamic_slice_in_dim(
            rows, off, int(c), axis=1).reshape(-1))
        off += int(c)
    return out


# ---------------------------------------------------------------------------
# Resolution chain: env > generation-gated tune DB > default.
# ---------------------------------------------------------------------------


def validate_threshold(raw) -> int:
    """Parse/validate a threshold value.  Any int is legal (<= 0 means
    packing off, per the HOROVOD_FUSION_THRESHOLD=0 convention)."""
    try:
        return int(raw)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"invalid fusion threshold {raw!r}; expected an integer byte "
            f"count ({ENV_VAR})") from e


def threshold_from_env(env=os.environ) -> int | None:
    """The explicit ``TPUFRAME_FUSION_THRESHOLD`` override, or None."""
    raw = env.get(ENV_VAR, "").strip()
    return validate_threshold(raw) if raw else None


def resolve(program: str | None = None, family: str | None = None,
            default: int | None = None) -> tuple:
    """``(threshold_bytes | None, source)`` for a step program: env
    override > tuning-DB winner (generation-gated; family
    ``fusion_threshold`` persisted by ``tune sweep --fusion``) >
    ``default``.  ``source`` is ``env``/``tune_db``/``default`` — emitted
    in the ``fusion_threshold`` run event so knob provenance is always on
    record.  None means fusion off (gradient reduction stays with the
    autodiff transpose + XLA combiner)."""
    env_val = threshold_from_env()
    if env_val is not None:
        return env_val, "env"
    if program or family:
        from tpuframe.tune import db as tune_db

        db_val = tune_db.resolve_fusion_threshold(program or "",
                                                  family=family)
        if db_val is not None:
            try:
                return validate_threshold(db_val), "tune_db"
            except ValueError:
                pass  # a stale DB row must never break a run
    return default, "default"


# ---------------------------------------------------------------------------
# Analysis-gate self-check.
# ---------------------------------------------------------------------------

# A minimal scheduled module shaped like a DEGENERATE fused strategy: two
# async bucket all-reduces, each consumed back-to-back (zero ops inside
# both start->done windows) even though each bucket's window could legally
# hold the other's work.  A strategy that declares its collectives
# overlapped MUST fail detect_exposed_comm on this program — the live
# gate's own positive, proving it is not blind to a fusion pass that
# issues windows and then wastes them.
_SEEDED_ZERO_OVERLAP_HLO = """\
HloModule seeded_fused_zero_overlap, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[32768], p1: f32[32768]) -> (f32[32768], f32[32768]) {
  %p0 = f32[32768]{0} parameter(0)
  %p1 = f32[32768]{0} parameter(1)
  %b0s = f32[32768]{0} all-reduce-start(f32[32768]{0} %p0), replica_groups={}, to_apply=%add
  %b0d = f32[32768]{0} all-reduce-done(f32[32768]{0} %b0s)
  %b1s = f32[32768]{0} all-reduce-start(f32[32768]{0} %p1), replica_groups={}, to_apply=%add
  %b1d = f32[32768]{0} all-reduce-done(f32[32768]{0} %b1s)
  ROOT %out = (f32[32768]{0}, f32[32768]{0}) tuple(%b0d, %b1d)
}
"""


def seeded_overlap_positive() -> list[str]:
    """jax-free positive: the seeded all-exposed fused program must FAIL
    the exposed-comm gate under a declared-overlapped strategy and stay
    report-only under an undeclared one."""
    from tpuframe.analysis import collective_graph as cg
    from tpuframe.analysis import shardflow

    problems: list[str] = []
    graph = cg.parse_graph(_SEEDED_ZERO_OVERLAP_HLO)
    found = shardflow.detect_exposed_comm(graph, True)
    if len(found) != 2 or any("back-to-back" not in f for f in found):
        problems.append(
            f"seeded fused zero-overlap positive: expected 2 zero-window "
            f"findings (both buckets consumed back-to-back) under a "
            f"declared-overlapped strategy, got {found!r} — the live gate "
            f"is blind")
    if shardflow.detect_exposed_comm(graph, False):
        problems.append(
            "seeded fused zero-overlap positive: an UNdeclared strategy "
            "must not fail on exposure (report-only contract broken)")
    return problems


def _census_problems() -> list[str]:
    """Bucket-census arithmetic over a synthetic mixed-dtype leaf list —
    pure shape math, no jax trace."""
    import numpy as np

    problems: list[str] = []
    leaves = [np.zeros((n,), dt) for n, dt in
              ((100, np.float32), (100, np.float32), (7, np.float32),
               (64, np.int8), (300, np.float32), (1, np.float32))]
    threshold = 512
    buckets = _bucketize(leaves, threshold)
    flat = [i for b in buckets for i in b]
    if flat != list(range(len(leaves))):
        problems.append(
            f"bucketize broke tree order: {buckets!r} is not an ordered "
            f"partition of {len(leaves)} leaves")
    for b in buckets:
        kinds = {_leaf_kind(leaves[i]) for i in b}
        if len(kinds) != 1:
            problems.append(f"bucket {b!r} mixes leaf kinds {kinds!r}")
        nbytes = sum(leaves[i].size * leaves[i].dtype.itemsize for i in b)
        if len(b) > 1 and nbytes > threshold:
            problems.append(
                f"bucket {b!r} holds {nbytes} B > threshold {threshold}")
    census = bucket_census(leaves, threshold)
    if census["n_buckets"] != len(buckets):
        problems.append("bucket_census disagrees with _bucketize on count")
    if census["total_bytes"] != sum(
            l.size * l.dtype.itemsize for l in leaves):
        problems.append("bucket_census lost bytes")
    if bucket_census(leaves, 0)["n_buckets"] != len(leaves):
        problems.append("threshold<=0 must census one bucket per leaf")
    return problems


def check_static() -> list[str]:
    """The jax-free legs of :func:`check` — safe for ``--selfcheck``:
    env parsing, bucket-census arithmetic, and the seeded zero-overlap
    positive that proves the declared_overlapped gate has teeth."""
    problems: list[str] = []
    try:
        threshold_from_env()
    except ValueError as e:
        problems.append(f"{ENV_VAR} is set to an invalid value: {e}")
    problems.extend(_census_problems())
    problems.extend(seeded_overlap_positive())
    return problems


def check() -> list[str]:
    """Self-check for the ``python -m tpuframe.analysis`` CI gate.
    Returns problem strings; [] means healthy.  Adds the psum-linearity
    identity (fused == staged == per-leaf under a real 8-member
    shard_map) on top of the static legs."""
    import numpy as np

    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    problems = check_static()
    if len(jax.devices()) < 2:
        problems.append(
            "fusion psum-linearity check needs a multi-device backend "
            "(run under the analysis CLI's forced-device child)")
        return problems
    n = len(jax.devices())
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n))
    rng = np.random.default_rng(7)
    tree = {
        "a": jnp.asarray(rng.normal(size=(2, 12)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32),
        "c": jnp.asarray(rng.normal(size=(3, 2)), jnp.float32),
    }

    def body(x):
        plain = jax.tree.map(lambda l: lax.psum(l, "data"), x)
        fused = fused_psum(x, "data", threshold_bytes=1 << 20)
        staged = staged_psum(x, "data", threshold_bytes=1 << 20)
        return plain, fused, staged

    from jax.sharding import PartitionSpec as P

    mapped = step_lib._shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P())
    plain, fused, staged = jax.jit(mapped)(tree)
    for k in tree:
        if not np.allclose(np.asarray(plain[k]), np.asarray(fused[k]),
                           rtol=1e-6, atol=1e-6):
            problems.append(
                f"psum linearity broken: fused_psum leaf {k!r} diverged "
                f"from per-leaf psum")
        if not np.allclose(np.asarray(plain[k]), np.asarray(staged[k]),
                           rtol=1e-6, atol=1e-6):
            problems.append(
                f"staged emission changed the math: staged_psum leaf "
                f"{k!r} diverged from per-leaf psum")
    return problems
