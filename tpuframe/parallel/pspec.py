"""Declarative parallelism spec — one string lowered onto the mesh.

``TPUFRAME_SPEC="dp=4,fsdp=2,tp=1;slices=2"`` names a complete
parallelism layout: the comma part declares the ICI axes of ONE slice
(in grammar keys — ``dp``/``fsdp``/``tp``/``pp``/``sp``/``ep``; values
are positive degrees, ``*`` on ``dp`` means "all remaining chips"), and
the optional ``;slices=N`` tail declares N such slices joined by DCN.
:func:`parse_spec` validates the grammar, :meth:`ParallelSpec.mesh_spec`
turns it into the hierarchical :class:`~tpuframe.parallel.mesh.MeshSpec`
(slice axis outermost, so only genuinely cross-slice collectives ride
the slow fabric), and :func:`lower` maps it onto the existing
``make_train_step`` seams — dp/zero1/fusion/hier stay orthogonal
modifiers instead of eight hand-wired strategies (ROADMAP item 2; the
composition view of arXiv:1909.09756 / arXiv:2011.03641).

Layer contract: this module imports only :mod:`tpuframe.parallel.mesh`
at the top level.  The analysis plane (shardflow's detectors and the
ICI/DCN byte split) is imported lazily inside :func:`check` — the gate
self-check — never at import time.
"""

from __future__ import annotations

import dataclasses
import os

SPEC_ENV = "TPUFRAME_SPEC"

#: grammar key -> mesh axis name (the order here is the canonical
#: formatting order; mesh axis order itself is fixed by mesh.AXES).
AXIS_KEYS = {
    "dp": "data",
    "fsdp": "fsdp",
    "tp": "model",
    "pp": "pipe",
    "sp": "seq",
    "ep": "expert",
}


class SpecError(ValueError):
    """A malformed, overcommitted, or unlowerable parallelism spec."""


@dataclasses.dataclass(frozen=True)
class ParallelSpec:
    """A parsed ``TPUFRAME_SPEC``.  ``dp == -1`` is the ``*`` wildcard
    ("all remaining chips"); every other degree must be positive."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    slices: int = 1

    def __post_init__(self):
        for key in AXIS_KEYS:
            v = getattr(self, key)
            if key == "dp" and v == -1:
                continue
            if not isinstance(v, int) or v < 1:
                raise SpecError(
                    f"axis {key}={v!r} must be a positive integer"
                    + (" (or * for all remaining chips)"
                       if key == "dp" else ""))
        if not isinstance(self.slices, int) or self.slices < 1:
            raise SpecError(f"slices={self.slices!r} must be a positive "
                            f"integer — a mesh spans at least one slice")

    def canonical(self) -> str:
        """Minimal round-trippable spelling: ``dp`` always prints (the
        spec is meaningless without a batch axis statement), other axes
        only at degree > 1, ``;slices=N`` only when hierarchical."""
        parts = [f"dp={'*' if self.dp == -1 else self.dp}"]
        parts += [f"{k}={getattr(self, k)}" for k in AXIS_KEYS
                  if k != "dp" and getattr(self, k) != 1]
        text = ",".join(parts)
        if self.slices > 1:
            text += f";slices={self.slices}"
        return text

    def mesh_spec(self):
        """The hierarchical :class:`MeshSpec` this spec declares."""
        from tpuframe.parallel import mesh as mesh_lib

        kw = {AXIS_KEYS[k]: getattr(self, k) for k in AXIS_KEYS}
        return mesh_lib.MeshSpec(slices=self.slices, **kw)

    def sizes(self, n_devices: int) -> dict:
        """Resolved per-axis sizes (mesh axis names), wildcard filled.
        Raises :class:`SpecError` on over/under-committed specs."""
        import numpy as np

        try:
            return self.mesh_spec().sizes(n_devices)
        except ValueError as e:
            fixed = int(np.prod([getattr(self, k) for k in AXIS_KEYS
                                 if getattr(self, k) != -1])) * self.slices
            if fixed > n_devices:
                raise SpecError(
                    f"spec '{self.canonical()}' is overcommitted: axis "
                    f"product {fixed} exceeds the {n_devices} available "
                    f"devices") from e
            raise SpecError(f"spec '{self.canonical()}' does not fit "
                            f"{n_devices} devices: {e}") from e

    def make_mesh(self, devices=None):
        """Build the declared hierarchical mesh over ``devices`` (default:
        every visible chip)."""
        from tpuframe.parallel import mesh as mesh_lib

        return mesh_lib.make_mesh(self.mesh_spec(), devices=devices)


def parse_spec(text: str) -> ParallelSpec:
    """Parse ``"dp=4,fsdp=2,tp=1;slices=2"`` into a :class:`ParallelSpec`.

    Grammar errors are :class:`SpecError` with the offending token named
    — an explicit spec (env or CLI) must fail loudly, never degrade."""
    if not isinstance(text, str) or not text.strip():
        raise SpecError("empty parallelism spec — expected e.g. "
                        "'dp=4,fsdp=2;slices=2'")
    text = "".join(text.split())  # whitespace is never meaningful
    head, sep, tail = text.partition(";")
    kw: dict[str, int] = {}
    if sep:
        skey, seq, sval = tail.partition("=")
        if skey != "slices" or not seq:
            raise SpecError(f"after ';' only 'slices=N' is allowed, "
                            f"got {tail!r}")
        try:
            kw["slices"] = int(sval)
        except ValueError:
            raise SpecError(f"slices={sval!r} is not an integer") from None
    if not head:
        raise SpecError(f"spec {text!r} has no axis part before ';'")
    for token in head.split(","):
        key, eq, val = token.partition("=")
        if not eq or not key or not val:
            raise SpecError(f"malformed axis token {token!r} — expected "
                            f"key=value")
        if key not in AXIS_KEYS:
            raise SpecError(f"unknown axis {key!r}; expected one of "
                            f"{sorted(AXIS_KEYS)}")
        if key in kw:
            raise SpecError(f"duplicate axis {key!r} in spec {text!r}")
        if val == "*":
            if key != "dp":
                raise SpecError(f"wildcard '*' is only allowed on dp, "
                                f"not {key!r}")
            kw[key] = -1
            continue
        try:
            kw[key] = int(val)
        except ValueError:
            raise SpecError(f"axis {key}={val!r} is not an integer "
                            f"(or * on dp)") from None
    return ParallelSpec(**kw)


def format_spec(spec: ParallelSpec) -> str:
    return spec.canonical()


def resolve(explicit: str | None = None) -> tuple:
    """``(ParallelSpec | None, source)`` with the framework's resolution
    discipline: an explicit argument wins, then the ``TPUFRAME_SPEC``
    env var, then ``(None, "default")`` — and an explicit ask that fails
    to parse raises (never a silent fallback)."""
    if explicit is not None:
        return parse_spec(explicit), "arg"
    raw = os.environ.get(SPEC_ENV)
    if raw is not None and raw.strip():
        return parse_spec(raw), "env"
    return None, "default"


# ---------------------------------------------------------------------------
# Lowering onto the make_train_step seams.
# ---------------------------------------------------------------------------


def lower(spec: ParallelSpec, mesh, state=None, *,
          weight_update: str = "replicated",
          fusion_threshold: int | None = None, tp_rules=None,
          grad_reduce: str | None = None, hier: str | None = None) -> dict:
    """Map a spec onto ``make_train_step`` kwargs.

    Three lowering classes exist, matching the step factory's own modes:

      * pure data-parallel (only ``dp``/``slices`` > 1) lowers to the
        shard_map path, where ``weight_update`` (zero1),
        ``fusion_threshold``, ``grad_reduce`` (``"adasum"``) and the
        two-level lowering (``hier``, :mod:`tpuframe.parallel.hier`)
        remain orthogonal modifiers — exactly the knobs ``zero1.resolve``
        / ``fusion.resolve`` / ``hier.resolve`` already feed.  adasum
        is its own wire pattern (the ppermute butterfly) and refuses the
        other modifiers, mirroring ``make_train_step``'s rules;
      * sequence-parallel specs (``sp`` > 1, weights replicated) stay on
        the shard_map path but partition the batch's sequence dim over
        the ``seq`` axis and widen the loss reduction to span it —
        activations shard, weights do not, so the shard_map modifiers
        whose byte accounting assumes batch-only sharding (zero1 /
        fusion / adasum / hier) do not compose;
      * weight-sharded specs (``fsdp``/``tp``/``ep`` > 1) lower to the
        auto-SPMD path via :func:`tpuframe.parallel.fsdp.state_shardings`
        over the declared (possibly hierarchical) mesh — ``state`` (a
        TrainState or its eval_shape) is required to build the sharding
        tree, ``tp``/``ep`` additionally require ``tp_rules`` (else the
        model/expert axis would silently replicate), and the
        shard_map-only modifiers do not compose (the partitioner owns
        the collectives).

    ``pp`` keeps its dedicated GPipe harness — declaring it here is a
    :class:`SpecError` pointing at :func:`lower_pp`, not a silent
    approximation.

    Returns the kwargs dict to splat into ``make_train_step(loss_fn,
    tx, mesh, **kwargs)``.
    """
    from tpuframe.parallel import mesh as mesh_lib

    declared = spec.sizes(mesh.devices.size)
    for axis, size in declared.items():
        if int(mesh.shape.get(axis, 1)) != int(size):
            raise SpecError(
                f"mesh axis {axis!r} has size {mesh.shape.get(axis, 1)} "
                f"but spec '{spec.canonical()}' declares {size} — lower "
                f"the spec onto the mesh it built (spec.make_mesh())")
    if spec.pp > 1:
        raise SpecError(
            f"spec '{spec.canonical()}': pp does not lower through "
            f"make_train_step — use lower_pp(), which drives the pp_lm "
            f"GPipe harness")
    grad_reduce = grad_reduce or "mean"
    hier = hier or "flat"
    if grad_reduce not in ("mean", "adasum"):
        raise SpecError(f"grad_reduce={grad_reduce!r} — expected 'mean' "
                        f"or 'adasum'")
    modified = (weight_update != "replicated"
                or fusion_threshold is not None or hier != "flat")
    if spec.fsdp > 1 or spec.tp > 1 or spec.ep > 1:
        if spec.sp > 1:
            raise SpecError(
                f"spec '{spec.canonical()}': sp is a shard_map batch "
                f"partition and does not compose with the auto-SPMD "
                f"weight-sharded lowering")
        if modified or grad_reduce != "mean":
            raise SpecError(
                f"spec '{spec.canonical()}': weight-sharded lowering is "
                f"auto-SPMD — zero1/fusion_threshold/adasum/"
                f"hier are shard_map modifiers and do not compose")
        if (spec.tp > 1 or spec.ep > 1) and tp_rules is None:
            raise SpecError(
                f"spec '{spec.canonical()}' shards weights over the "
                f"model/expert axis — pass tp_rules (e.g. "
                f"tp.rules_for_model(...)); without them the axis would "
                f"silently replicate")
        if state is None:
            raise SpecError(
                f"spec '{spec.canonical()}' shards weights — lowering "
                f"needs the TrainState (or its eval_shape) to build the "
                f"sharding tree")
        from tpuframe.parallel import fsdp as fsdp_lib

        shardings = fsdp_lib.state_shardings(state, mesh,
                                             tp_rules=tp_rules)
        return {
            "state_shardings": shardings,
            "batch_partition": mesh_lib.batch_spec(mesh=mesh),
        }
    if spec.sp > 1:
        if modified or grad_reduce != "mean":
            raise SpecError(
                f"spec '{spec.canonical()}': sp shards activations, not "
                f"weights — zero1/fusion_threshold/adasum/"
                f"hier assume batch-only sharding and do not compose")
        from jax.sharding import PartitionSpec as P

        axes = mesh_lib.batch_axes(mesh)
        return {
            "weight_update": weight_update,
            "fusion_threshold": fusion_threshold,
            "reduce_axes": (*axes, "seq"),
            "batch_partition": P(axes, "seq"),
        }
    if grad_reduce == "adasum" and modified:
        raise SpecError(
            f"spec '{spec.canonical()}': adasum's ppermute butterfly is "
            f"its own wire pattern — zero1/fusion_threshold/hier do not "
            f"compose")
    return {
        "weight_update": weight_update,
        "fusion_threshold": fusion_threshold,
        "grad_reduce": grad_reduce,
        "hier": hier,
        "reduce_axes": mesh_lib.batch_axes(mesh),
        "batch_partition": mesh_lib.batch_spec(mesh=mesh),
    }


def lower_pp(spec: ParallelSpec, mesh, model, tx, *, n_micro: int = 2,
             fused_xent: bool = False, remat_policy=None):
    """Lower a ``pp>1`` spec onto the GPipe harness.

    Pipeline parallelism cannot be expressed as ``make_train_step``
    kwargs — the microbatch loop restructures the step itself — so the
    spec grammar lowers it through :func:`tpuframe.parallel.pp_lm.
    make_pp_lm_step` instead.  ``model`` must be a ScanBlockLM whose
    ``num_layers`` is divisible by the declared ``pp`` degree (the
    harness re-checks and raises).  Returns the harness triple
    ``(step_fn_factory, place_state, place_batch)``."""
    declared = spec.sizes(mesh.devices.size)
    for axis, size in declared.items():
        if int(mesh.shape.get(axis, 1)) != int(size):
            raise SpecError(
                f"mesh axis {axis!r} has size {mesh.shape.get(axis, 1)} "
                f"but spec '{spec.canonical()}' declares {size} — lower "
                f"the spec onto the mesh it built (spec.make_mesh())")
    if spec.pp <= 1:
        raise SpecError(f"spec '{spec.canonical()}' declares no pipeline "
                        f"axis — lower_pp needs pp > 1")
    if spec.fsdp > 1 or spec.tp > 1 or spec.ep > 1 or spec.sp > 1:
        raise SpecError(
            f"spec '{spec.canonical()}': the GPipe harness composes pp "
            f"with dp only — fsdp/tp/ep/sp do not lower through it")
    from tpuframe.parallel import pp_lm

    return pp_lm.make_pp_lm_step(model, tx, mesh, n_micro=n_micro,
                                 fused_xent=fused_xent,
                                 remat_policy=remat_policy)


# ---------------------------------------------------------------------------
# Compile-only multi-slice topologies (the PR 3 trick, extended).
# ---------------------------------------------------------------------------


def topology_devices(topology: str = "v5e:2x2", *, slices: int = 1):
    """Compile-only TPU devices for a (possibly multi-slice) topology.

    Extends the ``TPU_SKIP_MDS_QUERY`` + ``get_topology_desc`` trick the
    tune sweeps use (single v5e:2x2) with PJRT's ``num_slices`` so
    cross-slice HLO is compilable on a machine with no TPU at all.
    Raises the underlying jax/PJRT error when this jax cannot express
    multi-slice topologies — callers gate with their capability idiom."""
    if slices < 1:
        raise SpecError(f"slices must be >= 1, got {slices}")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies

    kwargs = {"num_slices": int(slices)} if slices > 1 else {}
    return topologies.get_topology_desc(
        topology, platform="tpu", **kwargs).devices


# ---------------------------------------------------------------------------
# Gate self-check: grammar fuzz + a seeded replica-group-mismatch
# positive (the shardflow idiom — the gate refuses to run blind).
# ---------------------------------------------------------------------------

#: (text, canonical) pairs the grammar must round-trip byte-exactly.
_ROUNDTRIP_CASES = (
    ("dp=8", "dp=8"),
    ("dp=*", "dp=*"),
    (" dp = 4 , fsdp = 2 ", "dp=4,fsdp=2"),
    ("dp=4,fsdp=2,tp=1;slices=2", "dp=4,fsdp=2;slices=2"),
    ("dp=2,fsdp=2;slices=2", "dp=2,fsdp=2;slices=2"),
    ("fsdp=2", "dp=1,fsdp=2"),
    ("dp=1,tp=4;slices=4", "dp=1,tp=4;slices=4"),
    ("dp=*,ep=2", "dp=*,ep=2"),
    ("dp=2,tp=4", "dp=2,tp=4"),
    ("tp=2,dp=2", "dp=2,tp=2"),
    ("dp=*,tp=2", "dp=*,tp=2"),
    ("dp=2,pp=4", "dp=2,pp=4"),
    ("pp=2", "dp=1,pp=2"),
    ("dp=*,pp=2;slices=2", "dp=*,pp=2;slices=2"),
    ("dp=2,sp=4", "dp=2,sp=4"),
    ("sp=2,dp=*", "dp=*,sp=2"),
    ("ep=2,dp=4", "dp=4,ep=2"),
    ("dp=2,sp=2,ep=1,pp=1", "dp=2,sp=2"),
    ("dp=2,tp=2,pp=2;slices=2", "dp=2,tp=2,pp=2;slices=2"),
)

#: specs the parser must REJECT (malformed grammar).
_MALFORMED_CASES = (
    "", "   ", ";slices=2", "dp", "dp=", "=4", "dp=4,", "dp=x",
    "dp=0", "dp=-2", "fsdp=*", "bogus=2", "dp=2,dp=4",
    "dp=2;slices=0", "dp=2;slices=x", "dp=2;foo=2", "dp=2;slices=",
    "tp=*", "pp=*", "sp=*", "ep=*", "tp=0", "pp=-1", "sp=x",
    "ep=", "dp=2,tp=2,tp=4", "dp=2,sp=1.5",
)

#: (spec, n_devices) pairs that parse but must fail validation.
_OVERCOMMITTED_CASES = (
    ("dp=16", 8),
    ("dp=4,fsdp=4", 8),
    ("dp=4;slices=4", 8),
    ("dp=3", 8),
    ("tp=4,pp=4", 8),
    ("dp=2,sp=8", 8),
    ("dp=2,tp=2,ep=4", 8),
    ("dp=*,pp=16", 8),
    ("dp=2,tp=2;slices=4", 8),
)

# A hand-written program whose all-reduce groups ({0,1,2},{3,4,5},{6,7})
# cannot decompose over ANY product of the declared slice=2 x data=2 x
# fsdp=2 mesh axes — sizes are unequal AND 3 is no axis product.  The
# replica-group detector must flag it; if it stays quiet the gate is
# blind to exactly the mismatch the hierarchical mesh exists to catch.
_SEEDED_MISMATCH_HLO = """\
HloModule seeded_pspec_group_mismatch

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[65536]) -> f32[65536] {
  %p0 = f32[65536]{0} parameter(0)
  ROOT %ar = f32[65536]{0} all-reduce(f32[65536]{0} %p0), replica_groups={{0,1,2},{3,4,5},{6,7}}, to_apply=%add
}
"""

# The honest twin: a cross-slice program whose groups DO decompose over
# the same mesh — one 8-wide all-reduce (spans both slices) and one
# strided iota all-gather over the slice axis ({0,4},{1,5},{2,6},{3,7}).
# The detector must stay quiet AND the ICI/DCN split must put both on
# the DCN side (each group crosses the slice boundary at inner=4).
_SEEDED_CROSS_SLICE_HLO = """\
HloModule seeded_pspec_cross_slice

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[65536]) -> f32[131072] {
  %p0 = f32[65536]{0} parameter(0)
  %ar = f32[65536]{0} all-reduce(f32[65536]{0} %p0), replica_groups=[1,8]<=[8], to_apply=%add
  ROOT %ag = f32[131072]{0} all-gather(f32[65536]{0} %ar), replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
}
"""

_SEEDED_MESH = {"slice": 2, "data": 2, "fsdp": 2}


def _grammar_problems() -> list:
    problems = []
    for text, want in _ROUNDTRIP_CASES:
        try:
            spec = parse_spec(text)
        except SpecError as e:
            problems.append(f"pspec grammar: {text!r} must parse, "
                            f"got SpecError: {e}")
            continue
        got = spec.canonical()
        if got != want:
            problems.append(f"pspec grammar: {text!r} formats to {got!r}, "
                            f"expected {want!r}")
        elif parse_spec(got) != spec:
            problems.append(f"pspec grammar: {got!r} does not round-trip")
    for text in _MALFORMED_CASES:
        try:
            parse_spec(text)
        except SpecError:
            continue
        problems.append(f"pspec grammar: malformed {text!r} parsed "
                        f"without error — the validator is blind")
    for text, n in _OVERCOMMITTED_CASES:
        try:
            parse_spec(text).sizes(n)
        except SpecError:
            continue
        problems.append(f"pspec grammar: {text!r} validated on {n} "
                        f"devices — overcommit must be rejected")
    return problems


def check() -> list:
    """Gate self-check leg (``python -m tpuframe.analysis``): grammar
    fuzz over the pinned case tables, then the seeded replica-group
    positives against the hierarchical mesh — mismatch must be flagged,
    the valid cross-slice twin must be clean, and the ICI/DCN split must
    attribute the cross-slice bytes to DCN.  Any problem string means
    the pspec plane cannot be trusted and the gate fails."""
    problems = _grammar_problems()

    from tpuframe.analysis import collective_graph as cg
    from tpuframe.analysis import shardflow

    graph = cg.parse_graph(_SEEDED_MISMATCH_HLO)
    found = shardflow.detect_replica_groups(graph, _SEEDED_MESH)
    if not found:
        problems.append(
            "pspec seeded positive: groups {0,1,2},{3,4,5},{6,7} "
            "validated against the slice=2,data=2,fsdp=2 mesh — the "
            "replica-group detector is blind to the slice axis")
    clean_graph = cg.parse_graph(_SEEDED_CROSS_SLICE_HLO)
    noise = shardflow.detect_replica_groups(clean_graph, _SEEDED_MESH)
    if noise:
        problems.append(
            f"pspec seeded negative: the valid cross-slice program was "
            f"flagged — detector over-fires on the slice axis: {noise}")
    split = shardflow.comm_split(clean_graph, None,
                                 mesh_shape=_SEEDED_MESH, n_devices=8)
    if split["dcn_bytes"] <= 0:
        problems.append(
            f"pspec seeded split: cross-slice collectives attributed "
            f"{split['dcn_bytes']} DCN bytes — the ICI/DCN split is "
            f"blind to the slice boundary ({split})")
    if split["ici_bytes"] != 0:
        problems.append(
            f"pspec seeded split: a program whose every collective "
            f"crosses slices charged {split['ici_bytes']} bytes to ICI")
    return problems
