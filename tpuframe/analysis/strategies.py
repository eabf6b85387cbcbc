"""Auditable step programs, one per MULTICHIP parallelism strategy.

Each builder constructs the *real* framework step — the same
``make_train_step``/``pp_lm`` machinery production uses — over a tiny
model and a shapes-only state (``jax.eval_shape``; no parameter math
runs), lowers it AOT, and pairs the compiled program with the strategy's
declared :class:`~tpuframe.analysis.budgets.CommBudget`.  That makes the
communication-structure contract of every strategy checkable in seconds
on a CPU host: ``audit_strategy("lm-tensor-parallel")`` is the static
equivalent of burning a pod slice to discover a mis-sharding.

Capability gating: strategies whose step code needs jax features this
interpreter lacks (the vma/pcast machinery behind ring/Ulysses sequence
parallelism, GPipe PP and adasum on jax < 0.6) raise
:class:`Unavailable` with the missing-API reason instead of failing —
the CLI reports them as SKIP, tests ``pytest.skip`` on them, and on a
current jax they audit for real.  An Unavailable is a *capability*
statement, never a budget verdict.

Everything here expects a multi-device backend; on a plain CPU host run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CLI's
child process sets this up — see ``tpuframe.analysis.__main__``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

from tpuframe.analysis import budgets as budgets_lib
from tpuframe.analysis import hlo_audit

# Exception types that signal "this jax cannot express the strategy",
# as opposed to a real defect in the step program.
_CAPABILITY_ERRORS = (AttributeError, ImportError, NotImplementedError)


class Unavailable(Exception):
    """The strategy cannot be built in this environment (missing jax
    feature or too few devices) — a skip, not a failure."""


@dataclass(frozen=True)
class StrategyMeta:
    """What a strategy *declares* about itself, for the shardflow
    detectors: the mesh its replica groups must decompose over, the
    dtype its collectives are allowed to carry on the wire, and the
    per-leaf (dtype, full_dims, shard_dims) sharding expectations the
    accidental-replication detector checks entry parameters against."""

    mesh_shape: tuple[tuple[str, int], ...]
    wire_dtype: str = "f32"
    declared_leaves: tuple = ()    # ((hlo_dtype, full_dims, shard_dims),)
    #: the strategy claims its collectives overlap with compute (async
    #: start/done windows with work inside).  The exposed-communication
    #: detector FAILS a declared-overlapped strategy whose compiled
    #: program consumes a collective start back-to-back; undeclared
    #: strategies only get the exposure *reported* (CPU-compiled audits
    #: have no async scheduler, so nothing today may declare this —
    #: the future bucketed-fusion strategy is who the flag is for).
    declared_overlapped: bool = False

    @property
    def mesh_dict(self) -> dict:
        return dict(self.mesh_shape)


@dataclass
class StrategyAudit:
    """Outcome of auditing one strategy's step program."""

    name: str
    status: str                    # "ok" | "violation" | "unavailable"
    reason: str = ""               # set when unavailable
    violations: list[str] = field(default_factory=list)
    report: hlo_audit.CollectiveReport | None = None
    budget: budgets_lib.CommBudget | None = None
    param_bytes: int = 0
    compiled: object = None        # the AOT executable, for chained checks
    meta: StrategyMeta | None = None

    def __str__(self):
        if self.status == "unavailable":
            return f"SKIP {self.name}: {self.reason}"
        head = "PASS" if self.status == "ok" else "FAIL"
        body = self.report.summary() if self.report else "no report"
        tail = "".join(f"\n    {v}" for v in self.violations)
        return f"{head} {self.name}: {body}{tail}"


def _tree_bytes(tree) -> int:
    import jax
    import numpy as np

    return int(sum(np.prod(l.shape or (1,)) * np.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)))


#: numpy dtype name -> optimized-HLO spelling (what parse_graph sees).
_HLO_DTYPES = {
    "float64": "f64", "float32": "f32", "float16": "f16",
    "bfloat16": "bf16", "int64": "s64", "int32": "s32", "int16": "s16",
    "int8": "s8", "uint64": "u64", "uint32": "u32", "uint16": "u16",
    "uint8": "u8", "bool": "pred",
}


def _meta(mesh, *, wire_dtype: str = "f32",
          declared_leaves: tuple = (),
          declared_overlapped: bool = False) -> StrategyMeta:
    return StrategyMeta(
        mesh_shape=tuple((str(a), int(s)) for a, s in mesh.shape.items()),
        wire_dtype=wire_dtype, declared_leaves=declared_leaves,
        declared_overlapped=declared_overlapped)


def _declared_leaves(tree, shardings) -> tuple:
    """(hlo_dtype, full_dims, shard_dims) per state leaf — what the
    accidental-replication detector expects entry parameters to look
    like.  ``shardings`` is a matching pytree of NamedSharding."""
    import jax

    out = []
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)):
        dt = _HLO_DTYPES.get(str(getattr(leaf, "dtype", "")))
        if dt is None or not hasattr(sh, "shard_shape"):
            continue
        full = tuple(int(d) for d in leaf.shape)
        shard = tuple(int(d) for d in sh.shard_shape(full))
        out.append((dt, full, shard))
    return tuple(out)


def _leaves_from_sds(tree) -> tuple:
    """Same, for trees of ShapeDtypeStruct that carry their sharding."""
    import jax

    annotated = [(l, l.sharding) for l in jax.tree.leaves(tree)
                 if getattr(l, "sharding", None) is not None]
    return _declared_leaves([l for l, _ in annotated],
                            [s for _, s in annotated])


def _require_devices(n: int):
    import jax

    have = len(jax.devices())
    if have < n:
        raise Unavailable(
            f"needs {n} devices, have {have} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(python -m tpuframe.analysis does this automatically)")


def _lm_pieces(batch: int = 8, seq: int = 32, **cfg_kw):
    """Tiny TransformerLM + shapes-only state/batch for AOT lowering."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import step as step_lib

    model = models.get_model("transformer-lm", tiny=True, vocab_size=64,
                             max_seq=seq, **cfg_kw)
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, seq), jnp.int32))
    tx = optax.adamw(1e-3)

    def loss_fn(params, model_state, b, rng):
        logits = model.apply({"params": params}, b["input_ids"],
                             train=True, rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

    state = jax.eval_shape(lambda p: step_lib.TrainState.create(p, tx),
                           variables["params"])
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    example = (state, {"input_ids": ids, "labels": ids})
    param_bytes = _tree_bytes(variables["params"])
    # one activation tensor [B, S, H] in compute dtype (f32 for tiny)
    act_bytes = batch * seq * 64 * 4
    return model, loss_fn, tx, example, param_bytes, act_bytes


# --------------------------------------------------------------------------
# Builders.  Each returns
# (jitted_step, example_args, budget, param_bytes, meta).
#
# Every training parallelism strategy is SPEC-LOWERED: one generic
# builder parses a ``tpuframe.parallel.pspec`` string, builds the
# declared (possibly ICI×DCN) mesh, and lets ``pspec.lower`` /
# ``pspec.lower_pp`` pick the step seams — zero1/fusion/hier/adasum ride
# as orthogonal modifiers, tp/ep thread the model sharding rules, sp
# partitions the sequence dim, pp drives the GPipe harness.  The only
# hand-wired builder left is the serving decode audit, which is a decode
# program (no train step, no parallelism spec to lower).
# --------------------------------------------------------------------------


def _spec_budget(spec, pb: int, n_devices: int, *, weight_update: str,
                 padded: int | None, ab: int = 0,
                 seq_mode: str | None = None,
                 grad_reduce: str | None = None,
                 fusion_threshold: int | None = None,
                 hier: str | None = None,
                 n_inner: int = 1):
    """The declared CommBudget for a composed spec — the same per-kind
    ceilings the hand-wired family declared, picked by axis/modifier;
    the byte-exact pin lives in ``derived_budgets.json`` either way."""
    if spec.pp > 1:
        return budgets_lib.pp_budget(pb, ab, n_micro=2)
    if spec.ep > 1:
        return budgets_lib.ep_budget(pb, ab)
    if spec.tp > 1:
        return budgets_lib.tp_budget(pb, ab, num_layers=2)
    if spec.fsdp > 1:
        return budgets_lib.fsdp_budget(pb)
    if spec.sp > 1:
        if (seq_mode or "ring") == "ring":
            return budgets_lib.ring_sp_budget(pb, kv_bytes=2 * ab,
                                              sp_degree=spec.sp)
        return budgets_lib.ulysses_sp_budget(pb, ab)
    if grad_reduce == "adasum":
        return budgets_lib.adasum_budget(pb, n_devices)
    if hier == "hier":
        if weight_update == "zero1":
            return budgets_lib.hier_zero1_budget(padded, n_inner)
        return budgets_lib.hier_dp_budget(pb, n_inner)
    if weight_update == "zero1":
        # Bucketed fusion keeps the exact pad-to-multiple wire bytes —
        # the zero1 ceilings hold unchanged, fused or not.
        return budgets_lib.zero1_budget(padded)
    if fusion_threshold is not None:
        return budgets_lib.fused_dp_budget(pb)
    return budgets_lib.dp_budget(pb)


def _moe_pieces():
    """Tiny MoE TransformerLM + shapes-only state/batch for the ``ep``
    lowering: expert blocks every layer, aux loss threaded through the
    ``mutable=["aux_loss"]`` collection exactly as train.py does."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpuframe.models import losses
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.parallel import step as step_lib

    cfg = LMConfig.tiny(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, intermediate_size=64, max_seq=16,
                        moe_experts=4, moe_k=2, moe_every=1)
    model = TransformerLM(cfg)
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 16), jnp.int32))
    tx = optax.adamw(1e-3)

    def loss_fn(params, model_state, b, rng):
        logits, sown = model.apply({"params": params}, b["input_ids"],
                                   train=True, rngs={"dropout": rng},
                                   mutable=["aux_loss"])
        loss = losses.softmax_cross_entropy(logits, b["labels"])
        leaves = jax.tree.leaves(sown)
        aux = sum(leaves) / max(len(leaves), 1)
        return loss + cfg.moe_aux_weight * aux, ({}, {"moe_aux": aux})

    state = jax.eval_shape(lambda p: step_lib.TrainState.create(p, tx),
                           variables["params"])
    ids = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    example = (state, {"input_ids": ids, "labels": ids})
    pb = _tree_bytes(variables["params"])
    ab = 8 * 16 * 32 * 4
    return model, loss_fn, tx, example, pb, ab


def _pp_build(spec, mesh):
    """The ``pp`` lowering: ScanBlockLM with one block per stage, driven
    through :func:`tpuframe.parallel.pspec.lower_pp` (the GPipe
    harness).  Modifiers never reach here — the caller rejects them."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpuframe.models.transformer_lm import LMConfig, ScanBlockLM
    from tpuframe.parallel import pspec
    from tpuframe.parallel import step as step_lib

    cfg = LMConfig.tiny(vocab_size=64, hidden_size=32,
                        num_layers=spec.pp, num_heads=2,
                        intermediate_size=64, max_seq=16)
    model = ScanBlockLM(cfg)
    tx = optax.adamw(1e-3)
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 16), jnp.int32))
    n_micro = 2
    factory, _place_state, _place_batch = pspec.lower_pp(
        spec, mesh, model, tx, n_micro=n_micro)
    state = jax.eval_shape(lambda p: step_lib.TrainState.create(p, tx),
                           variables["params"])
    ids = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    step = factory(state)
    pb = _tree_bytes(variables["params"])
    ab = 8 * 16 * 32 * 4
    return (step, (state, {"input_ids": ids, "labels": ids}),
            budgets_lib.pp_budget(pb, ab, n_micro=n_micro), pb,
            _meta(mesh))


def _build_from_spec(spec_text: str, n_devices: int, *,
                     weight_update: str = "replicated",
                     seq_mode: str | None = None,
                     grad_reduce: str | None = None,
                     fusion_threshold: int | None = None,
                     hier: str | None = None,
                     declared_overlapped: bool = False,
                     devices=None):
    """Generic spec-lowered builder: ``spec_text`` (the
    ``TPUFRAME_SPEC`` grammar) -> hierarchical mesh -> lowered step.
    A spec whose axis product cannot fit ``n_devices`` is an
    :class:`Unavailable` (a skip — the spec is for a different world
    size), never a violation.  ``devices`` overrides the device list
    (the planner passes compile-only topology devices); ``seq_mode``
    picks ring vs Ulysses attention for ``sp`` specs; ``grad_reduce``
    threads the adasum modifier; ``fusion_threshold`` threads the
    bucketed-fusion modifier (tpuframe.parallel.fusion's staged pass);
    ``hier`` threads the two-level cross-slice lowering
    (tpuframe.parallel.hier), and
    ``declared_overlapped`` signs the overlap contract the
    exposed-comm detector then enforces live."""
    import dataclasses

    import jax

    from tpuframe.parallel import mesh as mesh_lib, pspec
    from tpuframe.parallel import step as step_lib

    spec = pspec.parse_spec(spec_text)
    try:
        spec.sizes(n_devices)
    except pspec.SpecError as e:
        raise Unavailable(str(e)) from e
    if devices is None:
        devices = jax.devices()[:n_devices]
    mesh = spec.make_mesh(devices=devices)
    if spec.pp > 1:
        if (weight_update != "replicated"
                or seq_mode or grad_reduce or fusion_threshold is not None):
            raise pspec.SpecError(
                f"spec '{spec.canonical()}': the GPipe lowering takes no "
                f"modifiers — zero1/seq_mode/adasum/fusion do not "
                f"compose")
        return _pp_build(spec, mesh)
    if spec.ep > 1:
        _, loss_fn, tx, (state, batch), pb, ab = _moe_pieces()
    elif spec.sp > 1:
        _, loss_fn, tx, (state, batch), pb, ab = _lm_pieces(
            seq_mode=seq_mode or "ring")
    else:
        _, loss_fn, tx, (state, batch), pb, ab = _lm_pieces()
    padded = None
    if weight_update == "zero1":
        from tpuframe.parallel import zero1 as zero1_lib

        n = zero1_lib.world_size(mesh, mesh_lib.batch_axes(mesh))
        opt = jax.eval_shape(
            lambda p: zero1_lib.init_opt_state(tx, p, n), state.params)
        state = dataclasses.replace(state, opt_state=opt)
        padded = zero1_lib.padded_bytes(state.params, n)
    tp_rules = None
    if spec.tp > 1 or spec.ep > 1:
        from tpuframe.parallel import tp as tp_lib

        tp_rules = tp_lib.rules_for_model("transformer-lm")
    kwargs = pspec.lower(spec, mesh, state, weight_update=weight_update,
                         tp_rules=tp_rules, grad_reduce=grad_reduce,
                         fusion_threshold=fusion_threshold, hier=hier)
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                    **kwargs)
    # In-slice world size for the two-level budgets: the batch-axis
    # product with the slice (DCN) axis divided out — the factor the
    # lowering's cross-slice leg shrinks by.
    sizes = dict(mesh.shape)
    n_slice = int(sizes.get(mesh_lib.SLICE_AXIS, 1))
    n_batch = 1
    for a in mesh_lib.batch_axes(mesh):
        n_batch *= int(sizes.get(a, 1))
    n_inner = max(1, n_batch // max(n_slice, 1))
    budget = _spec_budget(spec, pb, n_devices, weight_update=weight_update,
                          padded=padded, ab=ab,
                          seq_mode=seq_mode, grad_reduce=grad_reduce,
                          fusion_threshold=fusion_threshold,
                          hier=hier, n_inner=n_inner)
    shardings = kwargs.get("state_shardings")
    return (step, (state, batch), budget, pb,
            _meta(mesh,
                  declared_leaves=(_declared_leaves(state, shardings)
                                   if shardings is not None else ()),
                  declared_overlapped=declared_overlapped))


def _spec_name(spec_text: str, *, weight_update: str = "replicated",
               seq_mode: str | None = None,
               grad_reduce: str | None = None,
               fusion_threshold: int | None = None,
               hier: str | None = None) -> str:
    """Canonical strategy name for a composed spec: the spec's canonical
    spelling under a ``spec:`` prefix plus any modifiers — stable, so an
    auto-derived budget can be pinned in ``derived_budgets.json``."""
    from tpuframe.parallel import pspec

    name = f"spec:{pspec.parse_spec(spec_text).canonical()}"
    if weight_update != "replicated":
        name += f"+{weight_update}"
    if hier:
        name += f"+{hier}"
    if seq_mode:
        name += f"+{seq_mode}"
    if grad_reduce:
        name += f"+{grad_reduce}"
    if fusion_threshold is not None:
        name += f"+fused{int(fusion_threshold)}"
    return name


def register_spec_strategy(spec_text: str, *,
                           weight_update: str = "replicated",
                           seq_mode: str | None = None,
                           grad_reduce: str | None = None,
                           fusion_threshold: int | None = None,
                           hier: str | None = None,
                           declared_overlapped: bool = False) -> str:
    """Register a composed parallelism spec as a dynamic analysis
    strategy.  The name is the spec's canonical spelling under a
    ``spec:`` prefix (plus any modifiers) — stable, so its auto-derived
    budget can be pinned in ``derived_budgets.json`` like any named
    strategy's.  This is the ONE seam through which strategies enter the
    registry (TF120 lints everything else), and the ONE module allowed
    to sign ``declared_overlapped=True`` (TF122 lints everything else) —
    a strategy cannot claim compute/communication overlap without going
    through the audited fusion registration below."""
    import functools

    name = _spec_name(spec_text, weight_update=weight_update,
                      seq_mode=seq_mode, grad_reduce=grad_reduce,
                      fusion_threshold=fusion_threshold, hier=hier)
    STRATEGIES[name] = functools.partial(
        _build_from_spec, spec_text, weight_update=weight_update,
        seq_mode=seq_mode, grad_reduce=grad_reduce,
        fusion_threshold=fusion_threshold, hier=hier,
        declared_overlapped=declared_overlapped)
    return name


_warned_legacy: set = set()


def _warn_legacy(fn_name: str, spec_text: str) -> None:
    """Warn-once deprecation for the retired hand-wired constructors
    (the ``TPUFRAME_BENCH_REMAT`` alias idiom)."""
    if fn_name in _warned_legacy:
        return
    _warned_legacy.add(fn_name)
    import warnings

    warnings.warn(
        f"strategies.{fn_name} is a deprecated hand-wired constructor; "
        f"the strategy is spec-lowered now — use the {spec_text!r} "
        f"parallelism spec (tpuframe.parallel.pspec)",
        DeprecationWarning, stacklevel=3)


def _build_dp(n_devices: int):
    _warn_legacy("_build_dp", "dp=*")
    return _build_from_spec("dp=*", n_devices)


def _build_zero1(n_devices: int):
    """Deprecated alias: plain DP with the ZeRO-1 weight-update modifier
    (``weight_update="zero1"`` on the ``dp=*`` spec) — the audit proves
    the collective swap (no all-reduce above the scalar floor;
    reduce-scatter + all-gather at exactly the pad-to-multiple total)."""
    _warn_legacy("_build_zero1", "dp=*")
    return _build_from_spec("dp=*", n_devices, weight_update="zero1")


def _build_fsdp(n_devices: int):
    """Deprecated alias: the dp×fsdp layout is spec-lowered now."""
    _warn_legacy("_build_fsdp", "dp=*,fsdp=2")
    return _build_from_spec("dp=*,fsdp=2", n_devices)


def _build_tp(n_devices: int):
    """Deprecated alias: tensor parallelism is spec-lowered now (the
    ``tp=`` axis threads ``tp.rules_for_model`` automatically)."""
    tp = 4 if n_devices % 4 == 0 else 2
    _warn_legacy("_build_tp", f"dp=*,tp={tp}")
    return _build_from_spec(f"dp=*,tp={tp}", n_devices)


def _build_ring_sp(n_devices: int, seq_mode: str = "ring"):
    """Deprecated alias: sequence parallelism is spec-lowered now (the
    ``sp=`` axis partitions the batch's sequence dim; ``seq_mode`` picks
    ring vs Ulysses attention)."""
    sp = 4 if n_devices % 4 == 0 else 2
    _warn_legacy("_build_ring_sp", f"dp=*,sp={sp}")
    return _build_from_spec(f"dp=*,sp={sp}", n_devices, seq_mode=seq_mode)


def _build_ulysses(n_devices: int):
    return _build_ring_sp(n_devices, seq_mode="ulysses")


def _build_pp(n_devices: int):
    """Deprecated alias: pipeline parallelism is spec-lowered now (the
    ``pp=`` axis drives the GPipe harness via ``pspec.lower_pp``)."""
    pipe = 4 if n_devices % 4 == 0 else 2
    _warn_legacy("_build_pp", f"dp=*,pp={pipe}")
    return _build_from_spec(f"dp=*,pp={pipe}", n_devices)


def _build_ep(n_devices: int):
    """Deprecated alias: expert parallelism is spec-lowered now (the
    ``ep=`` axis shards the MoE expert blocks via the model rules)."""
    _warn_legacy("_build_ep", "dp=*,ep=2")
    return _build_from_spec("dp=*,ep=2", n_devices)


def _build_serve_decode(n_devices: int):
    """Plain-DP serving decode: KV slots sharded over ``data``, params
    replicated, ONE decode step (query length 1) — the exact program
    serve/engine.py compiles, audited for a zero-collective HLO."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.serve import engine as engine_lib
    from tpuframe.serve import kv_cache as kv

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n_devices))
    cfg = LMConfig.tiny(vocab_size=64)
    spec = kv.spec_for_model(cfg, slots=n_devices, capacity=64)
    model = TransformerLM(cfg)
    decode_fn = engine_lib.make_decode_fn(model)

    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
    pb = _tree_bytes(variables["params"])

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    sds = jax.ShapeDtypeStruct
    p_sds = jax.tree.map(lambda a: sds(a.shape, a.dtype, sharding=rep),
                         variables["params"])
    dtype = jnp.dtype(spec.dtype)
    cache_sds = tuple(
        (sds(spec.layer_shape(), dtype, sharding=row),
         sds(spec.layer_shape(), dtype, sharding=row))
        for _ in range(cfg.num_layers))
    example = (p_sds,
               sds((spec.slots, 1), jnp.int32, sharding=row),
               sds((spec.slots,), jnp.int32, sharding=row),
               cache_sds)
    return (jax.jit(decode_fn), example,
            budgets_lib.serve_decode_budget(pb), pb,
            _meta(mesh, declared_leaves=_leaves_from_sds(example)))


def _build_adasum(n_devices: int):
    """Deprecated alias: adasum is the ``grad_reduce`` modifier on the
    plain ``dp=*`` spec now."""
    _warn_legacy("_build_adasum", "dp=*")
    return _build_from_spec("dp=*", n_devices, grad_reduce="adasum")


#: MULTICHIP_r05.json strategy name -> builder.  Every training
#: strategy is spec-lowered (the partials below ARE the registration —
#: the old ``_build_*`` constructors survive only as warn-once
#: deprecated aliases).  The friendly names stay stable so the pinned
#: records in ``derived_budgets.json``/``derived_schedule.json`` keep
#: meaning the same programs.  ``spec:`` entries follow the
#: :func:`register_spec_strategy` naming convention; the composed
#: hierarchical entry is the PR 15 acceptance case — dp×fsdp inside
#: each slice, replicated over the DCN slice axis.  The serving decode
#: audit is the one non-spec entry (a decode program, not a train-step
#: parallelism).
STRATEGIES = {
    "dp": functools.partial(_build_from_spec, "dp=*"),
    "dp-zero1": functools.partial(_build_from_spec, "dp=*",
                                  weight_update="zero1"),
    "spec:dp=2,fsdp=2;slices=2": functools.partial(
        _build_from_spec, "dp=2,fsdp=2;slices=2"),
    "resnet-fsdp": functools.partial(_build_from_spec, "dp=*,fsdp=2"),
    "lm-tensor-parallel": functools.partial(_build_from_spec, "dp=*,tp=4"),
    "lm-seq-parallel": functools.partial(_build_from_spec, "dp=*,sp=4",
                                         seq_mode="ring"),
    "lm-seq-ulysses": functools.partial(_build_from_spec, "dp=*,sp=4",
                                        seq_mode="ulysses"),
    "pipeline-parallel": functools.partial(_build_from_spec, "dp=*,pp=4"),
    "expert-parallel": functools.partial(_build_from_spec, "dp=*,ep=2"),
    "dp-adasum": functools.partial(_build_from_spec, "dp=*",
                                   grad_reduce="adasum"),
    "serve-dp-decode": _build_serve_decode,
}

#: Bucket threshold the fused registry variants pin — mirrors
#: ``fusion.REGISTRY_THRESHOLD`` (duplicated so this module stays
#: jax-free at import; tests/test_fusion.py asserts the two agree).
_FUSED_REGISTRY_THRESHOLD = 128 * 1024

#: The overlapped bucketed-fusion registrations (ISSUE 18): the staged
#: pass (fusion.staged_psum / the bucketed zero1 scatter-gather) signs
#: the ``declared_overlapped`` contract, flipping detect_exposed_comm
#: from report-only to a live gate for exactly these two programs.
#: These are the ONLY sanctioned ``declared_overlapped=True`` call
#: sites — TF122 fails the gate on any other (see source_lint).
DP_FUSED = register_spec_strategy(
    "dp=*", fusion_threshold=_FUSED_REGISTRY_THRESHOLD,
    declared_overlapped=True)
DP_ZERO1_FUSED = register_spec_strategy(
    "dp=*", weight_update="zero1",
    fusion_threshold=_FUSED_REGISTRY_THRESHOLD,
    declared_overlapped=True)

#: The hierarchical two-level collective family (ISSUE 20): flat/hier
#: twins on the pure-DP multi-slice spec so the auto-derived budget pins
#: document the DCN byte column dropping by n_inner against the SAME
#: spec, model and world.  The zero1 composition is the acceptance
#: carrier: flat ZeRO-1 pays two full-size DCN collectives per step (rs
#: in, ag out), the two-level shape two shard-size ones.
_HIER_SPEC = "dp=*;slices=2"
HIER_FLAT = register_spec_strategy(_HIER_SPEC)
HIER_DP = register_spec_strategy(_HIER_SPEC, hier="hier")
HIER_ZERO1_FLAT = register_spec_strategy(
    _HIER_SPEC, weight_update="zero1")
HIER_ZERO1 = register_spec_strategy(
    _HIER_SPEC, weight_update="zero1", hier="hier")


def _overlap_compile_opts(meta) -> dict | None:
    """A strategy that signs ``declared_overlapped`` owns its bucketing:
    the staged fusion pass already packed the gradient wire, so XLA's
    all-reduce combiner is asked to keep its hands off via the generic
    DebugOptions field ("gpu" is historical naming — see
    parallel/tuning.py).  Backends that read the field (CPU XLA here)
    honor it; the v5e libtpu pin accepts-but-ignores it and re-merges
    the buckets into one end-of-step collective anyway (no ``xla_tpu_*``
    spelling exists: "No such compile option"), so on that backend the
    live gate (correctly) rules the declaration vacuously false —
    PERF.md §26 records the measurement.  Rides the compile request
    per-compile (the TF106-sanctioned path), never XLA_FLAGS."""
    if meta is None or not getattr(meta, "declared_overlapped", False):
        return None
    return {"xla_gpu_all_reduce_combine_threshold_bytes": 0}


def audit_spec(spec_text: str, *, n_devices: int,
               weight_update: str = "replicated",
               seq_mode: str | None = None,
               grad_reduce: str | None = None,
               fusion_threshold: int | None = None,
               hier: str | None = None,
               devices=None, name: str | None = None) -> StrategyAudit:
    """Audit an UNREGISTERED spec candidate — the ``tune plan`` seam.

    Same build/compile/budget-check pipeline as :func:`audit_strategy`,
    but over an ad-hoc spec string instead of a registry entry, and with
    an optional explicit device list so the planner can compile against
    ``pspec.topology_devices`` instead of the local backend.  The
    planner enumerating hundreds of candidates goes through here so it
    never hand-builds a :class:`StrategyMeta` (TF120's rule).  A
    ``fusion_threshold`` candidate runs the staged bucketed pass and is
    automatically declared overlapped — the same contract the registered
    fused variants sign."""
    label = name or _spec_name(spec_text, weight_update=weight_update,
                               seq_mode=seq_mode, grad_reduce=grad_reduce,
                               fusion_threshold=fusion_threshold,
                               hier=hier)
    try:
        if devices is None:
            _require_devices(n_devices)
        step, example, budget, pb, meta = _build_from_spec(
            spec_text, n_devices, weight_update=weight_update,
            seq_mode=seq_mode, grad_reduce=grad_reduce,
            fusion_threshold=fusion_threshold, hier=hier,
            declared_overlapped=fusion_threshold is not None,
            devices=devices)
        report, compiled = hlo_audit.audit_jitted(
            step, *example, compiler_options=_overlap_compile_opts(meta))
    except Unavailable as e:
        return StrategyAudit(name=label, status="unavailable",
                             reason=str(e))
    except _CAPABILITY_ERRORS as e:
        return StrategyAudit(
            name=label, status="unavailable",
            reason=f"{type(e).__name__}: {e} (jax {_jax_version()} lacks "
                   f"an API this strategy's step code needs)")
    violations = budgets_lib.check_budget(report, budget)
    return StrategyAudit(
        name=label, status="ok" if not violations else "violation",
        violations=violations, report=report, budget=budget,
        param_bytes=pb, compiled=compiled, meta=meta)


def audit_strategy(name: str, n_devices: int = 8) -> StrategyAudit:
    """Build, AOT-compile and budget-check one strategy's step program."""
    if name not in STRATEGIES:
        raise ValueError(f"unknown strategy {name!r}; "
                         f"have {sorted(STRATEGIES)}")
    try:
        _require_devices(n_devices)
        step, example, budget, pb, meta = STRATEGIES[name](n_devices)
        report, compiled = hlo_audit.audit_jitted(
            step, *example, compiler_options=_overlap_compile_opts(meta))
    except Unavailable as e:
        return StrategyAudit(name=name, status="unavailable",
                             reason=str(e))
    except _CAPABILITY_ERRORS as e:
        return StrategyAudit(
            name=name, status="unavailable",
            reason=f"{type(e).__name__}: {e} (jax {_jax_version()} lacks "
                   f"an API this strategy's step code needs)")
    violations = budgets_lib.check_budget(report, budget)
    return StrategyAudit(
        name=name, status="ok" if not violations else "violation",
        violations=violations, report=report, budget=budget,
        param_bytes=pb, compiled=compiled, meta=meta)


def audit_all(n_devices: int = 8,
              names: tuple[str, ...] | None = None) -> list[StrategyAudit]:
    return [audit_strategy(n, n_devices)
            for n in (names or tuple(STRATEGIES))]


def _jax_version() -> str:
    import jax

    return jax.__version__
