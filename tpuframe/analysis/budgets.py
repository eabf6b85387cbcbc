"""Declared per-strategy communication budgets (Layer 1's policy half).

A :class:`CommBudget` is the *declared* communication structure of a
parallelism strategy: which collective kinds its step program is allowed
to contain and how many bytes each may move per step.  The mechanism
(``tpuframe.analysis.hlo_audit``) reports what the compiler actually
emitted; :func:`check_budget` compares the two.  A sharding-annotation
mistake that makes GSPMD materialize a full all-gather then fails CI
with the offending instruction's shape and replica groups, instead of
burning pod time (the round-5 failure mode this module institutionalizes).

Budgets are declared as *multipliers over program-derived sizes* (param
bytes, activation bytes), not absolute numbers, so the same declaration
covers the tiny CI-audit models and the real configs.  The multipliers
are deliberately generous (2-4x the textbook volume): the check exists
to catch the *class* error — a forbidden collective kind, or an
activation-sized transfer where a param-sized one was declared — not to
police 10% regressions (that is the perf rigs' job, PERF.md §7).

Declaring a budget for a new strategy (docs/DESIGN.md "analysis"):

    budget = CommBudget(
        name="my-strategy",
        allowed={"all-reduce": 2 * param_bytes,
                 "collective-permute": 4 * act_bytes},
        ignore_below=64 * 1024,   # scalar metrics / counters are free
    )

Every kind absent from ``allowed`` is forbidden outright (above the
``ignore_below`` floor) — new communication patterns must be declared,
never inherited silently.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from tpuframe.analysis.hlo_audit import COLLECTIVE_KINDS, CollectiveReport

# Ops smaller than this are metric scalars, step counters, degenerate
# single-element syncs — never the failure class this gate hunts.
DEFAULT_IGNORE_BELOW = 64 * 1024


@dataclass(frozen=True)
class CommBudget:
    """Declared per-step communication ceiling for one strategy."""

    name: str
    # kind -> max bytes per step (None = allowed, unlimited).  Kinds not
    # present are forbidden above ``ignore_below``.
    allowed: dict[str, int | None] = field(default_factory=dict)
    max_total_bytes: int | None = None
    ignore_below: int = DEFAULT_IGNORE_BELOW
    notes: str = ""

    def __post_init__(self):
        bad = set(self.allowed) - set(COLLECTIVE_KINDS)
        if bad:
            raise ValueError(f"unknown collective kind(s) {sorted(bad)}; "
                             f"expected {COLLECTIVE_KINDS}")


def check_budget(report: CollectiveReport, budget: CommBudget) -> list[str]:
    """Violation messages (empty = the program fits its declaration)."""
    violations: list[str] = []
    sig = report.filter(budget.ignore_below)
    by_kind = sig.bytes_by_kind()
    for kind, total in sorted(by_kind.items()):
        if kind not in budget.allowed:
            ops = [op for op in sig.ops if op.kind == kind]
            worst = max(ops, key=lambda op: op.bytes)
            violations.append(
                f"[{budget.name}] undeclared collective kind {kind!r}: "
                f"{len(ops)} op(s), {total / 1e6:.3f} MB "
                f"(largest: {worst})")
            continue
        cap = budget.allowed[kind]
        if cap is not None and total > cap:
            violations.append(
                f"[{budget.name}] {kind} budget exceeded: "
                f"{total / 1e6:.3f} MB > declared {cap / 1e6:.3f} MB")
    if (budget.max_total_bytes is not None
            and sig.total_bytes > budget.max_total_bytes):
        violations.append(
            f"[{budget.name}] total collective bytes exceeded: "
            f"{sig.total_bytes / 1e6:.3f} MB > declared "
            f"{budget.max_total_bytes / 1e6:.3f} MB")
    return violations


# ---------------------------------------------------------------------------
# Strategy declarations — one per parallelism strategy the framework
# trains with (the MULTICHIP_r*.json strategy set).  ``param_bytes`` is
# the f32 byte size of the model parameters (gradient wire dtype);
# ``act_bytes`` the byte size of one sharded activation tensor
# [local_batch, seq, hidden] in compute dtype.
# ---------------------------------------------------------------------------


def dp_budget(param_bytes: int, name: str = "dp") -> CommBudget:
    """Pure data parallelism (Horovod parity): ONE class of collective —
    gradient all-reduce ≲ param bytes (f32), plus metric scalars."""
    return CommBudget(
        name=name,
        allowed={"all-reduce": int(2.0 * param_bytes)},
        notes="grad all-reduce + BN-stat/metric reductions only",
    )


def fused_dp_budget(param_bytes: int,
                    name: str = "dp-fused") -> CommBudget:
    """Plain DP with the explicit bucketed-fusion pass
    (tpuframe.parallel.fusion's staged psum): the same single class of
    collective as :func:`dp_budget` — gradient all-reduce ≲ param bytes
    — but emitted as one op per ≤threshold-byte bucket instead of the
    combiner's grouping, so the floor drops to 1 KiB: EVERY bucket is a
    declared window the schedule records pin (the nonzero-interior
    contract), not just the ones over the 64 KiB scalar floor."""
    return CommBudget(
        name=name,
        allowed={"all-reduce": int(2.0 * param_bytes)},
        ignore_below=1024,
        notes="bucketed grad all-reduce (staged fusion pass) + metric "
              "scalars; every bucket counts above the 1 KiB floor",
    )


def zero1_budget(padded_param_bytes: int, name: str = "dp-zero1") -> CommBudget:
    """ZeRO-1 weight-update sharding (arXiv:2004.13336, the zero1 path):
    the gradient all-reduce is REPLACED by reduce-scatter (grads in — the
    operand is the full padded gradient, which is what crosses the wire)
    plus tiled all-gather (updated params out).  Unlike the other
    budgets' generous multipliers, the ceilings here are EXACT — the
    audit is the proof the collective swap happened, so the declared
    bytes are the pad-to-multiple layout's byte total and nothing more —
    and the floor drops to 1 KiB so even tiny per-leaf collectives count
    (scalar loss/metric/grad-norm reductions stay free).  Any all-reduce
    above that floor is the defect class itself."""
    return CommBudget(
        name=name,
        allowed={"reduce-scatter": int(padded_param_bytes),
                 "all-gather": int(padded_param_bytes)},
        ignore_below=1024,
        notes="grad reduce-scatter in + param all-gather out, exact "
              "pad-to-multiple bytes; all-reduce forbidden above the "
              "1 KiB scalar floor (arXiv:2004.13336 wire pattern)",
    )


def hier_dp_budget(param_bytes: int, n_inner: int,
                   name: str = "dp-hier") -> CommBudget:
    """Plain DP under the two-level lowering (tpuframe.parallel.hier,
    arXiv:1909.09756 recipe): the flat grad all-reduce is REPLACED by
    in-slice reduce-scatter(mean) + in-slice all-gather (ICI, full
    bytes) around a cross-slice all-reduce of the 1/``n_inner`` shard —
    the ONLY collective that touches DCN, which is the byte drop this
    budget documents: its ceiling is ``param_bytes / n_inner`` plus a
    half-size fp allowance for sub-floor leaves (they keep the flat
    cross-slice mean — full bytes on DCN, but tiny).  The floor drops to
    1 KiB so the audit sees the shard-sized DCN leg on the tiny audit
    model."""
    return CommBudget(
        name=name,
        allowed={"reduce-scatter": int(1.5 * param_bytes),
                 "all-gather": int(1.5 * param_bytes),
                 "all-reduce": int((1 / n_inner + 0.5) * param_bytes)},
        ignore_below=1024,
        notes="two-level grad mean: in-slice rs+ag (ICI) around a "
              "1/n_inner cross-slice all-reduce (the sole DCN leg); "
              "sub-floor leaves keep the flat cross-slice mean",
    )


def hier_zero1_budget(padded_param_bytes: int, n_inner: int,
                      name: str = "dp-zero1-hier") -> CommBudget:
    """ZeRO-1 under the two-level lowering: the grad reduce-scatter and
    the param all-gather each become a two-stage pair — in-slice over
    ICI at full bytes, cross-slice over DCN at 1/``n_inner`` of them.
    Like :func:`zero1_budget` the ceilings are EXACT, not generous: each
    kind totals ``padded * (1 + 1/n_inner)`` (the in-slice stage's full
    padded bytes plus the cross-slice stage's shard), so the audit
    proves both that the collective swap happened AND that only the
    shard-sized stage is left to cross DCN.  All-reduce stays forbidden
    above the 1 KiB scalar floor."""
    ceiling = int(padded_param_bytes * (1 + 1 / n_inner))
    return CommBudget(
        name=name,
        allowed={"reduce-scatter": ceiling, "all-gather": ceiling},
        ignore_below=1024,
        notes="two-stage rs(mean) in + two-stage ag out, exact "
              "padded*(1+1/n_inner) bytes per kind; only the shard-"
              "sized cross-slice stage rides DCN; all-reduce forbidden "
              "above the 1 KiB scalar floor",
    )


def serve_decode_budget(param_bytes: int = 0,
                        name: str = "serve-dp-decode") -> CommBudget:
    """Plain-DP serving decode: params replicated, KV slots sharded over
    data — NO collective has any business in the step.  Unlike training
    DP there is no gradient to sync; every byte of cross-replica traffic
    is the partitioner inventing communication a per-token latency
    budget cannot afford, so the allowed set is empty (``param_bytes``
    accepted for the uniform ``strategy_budget`` call shape; a
    zero-collective ceiling does not scale with it)."""
    del param_bytes
    return CommBudget(
        name=name,
        allowed={},
        notes="serving decode is replica-local by construction; any "
              "collective above the scalar floor is a partitioning bug",
    )


def fsdp_budget(param_bytes: int, name: str = "resnet-fsdp") -> CommBudget:
    """ZeRO/FSDP over data x fsdp: params all-gathered before use (fwd +
    bwd re-gather ⇒ ~2x param bytes), grads reduce-scattered (~1x) and
    cross-replica all-reduced over the data axis (~1x).  GSPMD may fold
    some of these into each other; ceilings are per-kind unions."""
    return CommBudget(
        name=name,
        allowed={
            "all-gather": int(3.0 * param_bytes),
            "reduce-scatter": int(2.0 * param_bytes),
            "all-reduce": int(3.0 * param_bytes),
        },
        notes="ZeRO-3 wire pattern (arXiv:2004.13336 weight-update "
              "sharding generalized)",
    )


def tp_budget(param_bytes: int, act_bytes: int, num_layers: int,
              name: str = "lm-tensor-parallel") -> CommBudget:
    """Megatron-style TP: per layer, activation-sized all-reduces (2 fwd
    + 2 bwd) over the model axis, plus the gradient sync over data.
    GSPMD sometimes chooses all-gather+dynamic-slice over an all-reduce
    pair, so activation-sized all-gathers are declared too."""
    act_traffic = int(8.0 * act_bytes * max(num_layers, 1))
    return CommBudget(
        name=name,
        allowed={
            "all-reduce": int(3.0 * param_bytes) + act_traffic,
            "all-gather": int(2.0 * param_bytes) + act_traffic,
            "reduce-scatter": int(2.0 * param_bytes) + act_traffic,
        },
        notes="activation all-reduces per layer + grad sync",
    )


def ring_sp_budget(param_bytes: int, kv_bytes: int, sp_degree: int,
                   name: str = "lm-seq-parallel") -> CommBudget:
    """Ring-attention SP: the KV pair rotates sp-1 hops per attention
    call, forward and backward (plus dq/dkv return traffic) — the only
    collective-permute user among the strategies.  Grad sync rides the
    usual all-reduce."""
    hops = max(sp_degree - 1, 1)
    return CommBudget(
        name=name,
        allowed={
            "collective-permute": int(8.0 * kv_bytes * hops),
            "all-reduce": int(3.0 * param_bytes),
            # shard_map boundary resharding of tiny carries
            "all-gather": int(1.0 * param_bytes),
        },
        notes="ppermute KV ring (fwd+bwd) + grad all-reduce",
    )


def ulysses_sp_budget(param_bytes: int, act_bytes: int,
                      name: str = "lm-seq-ulysses") -> CommBudget:
    """Ulysses SP: all_to_all head<->seq reshards (2 fwd + 2 bwd per
    attention, each moving the activation once) + grad all-reduce."""
    return CommBudget(
        name=name,
        allowed={
            "all-to-all": int(8.0 * act_bytes),
            "all-reduce": int(3.0 * param_bytes),
            "all-gather": int(1.0 * param_bytes),
        },
        notes="all_to_all head resharding + grad all-reduce",
    )


def pp_budget(param_bytes: int, act_bytes: int, n_micro: int,
              name: str = "pipeline-parallel") -> CommBudget:
    """GPipe PP: microbatch activations hop stage-to-stage via
    collective-permute (fwd + bwd per microbatch), block grads sync over
    data; the scan-stacked blocks may be all-gathered for the update."""
    return CommBudget(
        name=name,
        allowed={
            "collective-permute": int(8.0 * act_bytes * max(n_micro, 1)),
            "all-reduce": int(3.0 * param_bytes),
            "all-gather": int(3.0 * param_bytes),
            "reduce-scatter": int(2.0 * param_bytes),
        },
        notes="stage-boundary ppermute + grad sync",
    )


def ep_budget(param_bytes: int, act_bytes: int,
              name: str = "expert-parallel") -> CommBudget:
    """MoE EP: token dispatch/combine across the expert axis (all-to-all
    in the planned program; GSPMD's dense dispatch may lower to
    all-gather + masked compute at CI scale) + grad sync."""
    return CommBudget(
        name=name,
        allowed={
            "all-to-all": int(8.0 * act_bytes),
            "all-gather": int(3.0 * param_bytes) + int(8.0 * act_bytes),
            "reduce-scatter": int(2.0 * param_bytes),
            "all-reduce": int(3.0 * param_bytes) + int(8.0 * act_bytes),
        },
        notes="token dispatch/combine + grad sync",
    )


def adasum_budget(param_bytes: int, n_devices: int,
                  name: str = "dp-adasum") -> CommBudget:
    """DP with the Adasum ppermute XOR butterfly: log2(n) exchange rounds
    each moving the full gradient, instead of one all-reduce."""
    rounds = max((n_devices - 1).bit_length(), 1)
    return CommBudget(
        name=name,
        allowed={
            "collective-permute": int(3.0 * param_bytes * rounds),
            "all-reduce": int(2.0 * param_bytes),
        },
        notes="ppermute butterfly grad combine (hvd.Adasum parity)",
    )


def strategy_budget(strategy: str, **sizes) -> CommBudget:
    """Budget for a MULTICHIP strategy name from program-derived sizes."""
    builders = {
        "dp": dp_budget,
        "dp-zero1": zero1_budget,
        "serve-dp-decode": serve_decode_budget,
        "resnet-fsdp": fsdp_budget,
        "lm-seq-parallel": ring_sp_budget,
        "lm-seq-ulysses": ulysses_sp_budget,
        "lm-tensor-parallel": tp_budget,
        "pipeline-parallel": pp_budget,
        "expert-parallel": ep_budget,
        "dp-adasum": adasum_budget,
    }
    if strategy not in builders:
        raise ValueError(f"no declared budget for strategy {strategy!r}; "
                         f"have {sorted(builders)}")
    return builders[strategy](**sizes)


# ---------------------------------------------------------------------------
# Known capability exclusions the budgets must cite instead of papering
# over (DESIGN.md invariant 2: no silent fallbacks at capability
# boundaries).  Each entry is checkable against the gate that causes it.
# ---------------------------------------------------------------------------

#: Shapes the fused conv+BN backward's VMEM gate excludes by design.
#: First entry: ResNet-50 layer4's downsample (K=1024 -> C=2048): the
#: resident weight block + f32 accumulator alone are K*C*6 B ≈ 12.6 MB,
#: over the 10 MB budget, so that pair keeps the plain-XLA composition
#: (numerics identical; see tpuframe/ops/fused_conv_bn.py and PERF.md
#: §11).  The audit cites this list so "fused BN covers the 1x1 convs"
#: claims stay honest about the one shape it does not.
KNOWN_VMEM_EXCLUSIONS: tuple[dict, ...] = (
    {
        "op": "fused_conv_bn",
        "site": "ResNet-50 layer4 downsample",
        "shape": {"h": 7, "w": 7, "n": 256, "k": 1024, "c": 2048},
        "reason": "K*C*6 = 12.58 MB resident weight+accumulator exceeds "
                  "the 10 MB VMEM budget; pair falls back to the "
                  "byte-identical XLA composition",
    },
)


def check_known_exclusions() -> list[str]:
    """Cross-check every KNOWN_VMEM_EXCLUSIONS entry against the actual
    gate: an entry whose shape became supported (or a gate change that
    silently widened an exclusion) must update this registry + PERF.md."""
    problems = []
    for entry in KNOWN_VMEM_EXCLUSIONS:
        if entry["op"] == "fused_conv_bn":
            from tpuframe.ops import fused_conv_bn

            s = entry["shape"]
            if fused_conv_bn.supported(s["h"], s["w"], s["n"], s["k"],
                                       s["c"]):
                problems.append(
                    f"{entry['site']}: registered as VMEM-excluded but "
                    f"fused_conv_bn.supported({s}) is now True — update "
                    f"KNOWN_VMEM_EXCLUSIONS and PERF.md §11")
    return problems
