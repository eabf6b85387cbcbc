"""Static SPMD/collective analysis — the no-chip CI gate.

Rounds 4-5 established that this framework's worst failure mode is
*silent*: interpret-mode pallas kernels masquerading as Mosaic compiles,
GSPMD materializing an unplanned all-gather from one wrong sharding
annotation, a VMEM gate quietly excluding the one shape the docs said it
covered.  All of those are *static* properties of the traced/compiled
program — visible on a CPU host with AOT lowering, before any chip time
is spent (the same argument as GSPMD's weight-update-sharding analysis
and Horovod's tensor-order consistency checks: in SPMD systems the
communication structure is decided at compile time, so check it there).

Four layers, all offline:

  1. :mod:`tpuframe.analysis.hlo_audit` — parse every collective
     (all-reduce, all-gather, reduce-scatter, all-to-all,
     collective-permute) out of compiled-HLO / StableHLO text with
     shapes, dtypes and replica groups; compute per-step byte volumes;
     check them against the per-strategy communication budgets declared
     in :mod:`tpuframe.analysis.budgets`.
  2. :mod:`tpuframe.analysis.jaxpr_checks` — audit the traced program:
     f32 upcasts inside bf16 regions, huge trace-time constant capture,
     donation leaks (declared-donated buffers the compiled module does
     not alias).
  3. :mod:`tpuframe.analysis.source_lint` — an AST pass over the source
     catching the JAX footguns rounds 4-5 hit by hand: host conversions
     on tracers, Python control flow on tracer values, timing without
     ``block_until_ready``, pallas calls without an explicit
     interpret/Mosaic decision.
  4. :mod:`tpuframe.analysis.collective_graph` +
     :mod:`tpuframe.analysis.shardflow` — the *structural* layer
     (analysis v2): the optimized HLO parsed into a typed def-use graph
     of collectives/parameters, detectors for redundant collective
     pairs, wire-dtype violations, accidental replication and
     replica-group/mesh inconsistency, and per-strategy derived budgets
     drift-checked against the checked-in ``derived_budgets.json``.
     Analysis v3 adds the *schedule* plane on the same graph: async
     start/done overlap windows, an exposed-communication detector, a
     buffer-liveness peak-HBM estimator pinned in
     ``derived_schedule.json``, and a roofline overlap-potential score
     per strategy.

CLI: ``python -m tpuframe.analysis`` (see ``__main__.py``) runs all
four layers CPU-only and exits non-zero on any finding — the CI gate.
Runtime registration: ``tpuframe.obs.spmd_check.check_step_program``
accepts a ``budget=`` so the startup hash check and the collective
audit run off the same lowering.
"""

from tpuframe.analysis.budgets import (  # noqa: F401
    CommBudget,
    KNOWN_VMEM_EXCLUSIONS,
    check_budget,
    strategy_budget,
)
from tpuframe.analysis.collective_graph import (  # noqa: F401
    CollectiveGraph,
    CollectiveWindow,
    Computation,
    LivenessReport,
    Node,
    ScheduleView,
    graph_of_compiled,
    liveness,
    parse_graph,
    schedule_view,
)
from tpuframe.analysis.hlo_audit import (  # noqa: F401
    CollectiveOp,
    CollectiveReport,
    allreduce_payload,
    audit_compiled,
    audit_jitted,
    parse_collectives,
)
from tpuframe.analysis.jaxpr_checks import (  # noqa: F401
    DonationReport,
    audit_donation,
    find_f32_matmuls,
    find_large_constants,
    parse_input_output_alias,
)
from tpuframe.analysis.shardflow import (  # noqa: F401
    build_report,
    compare_reports,
    derive_budget,
    derived_for,
    overlap_score,
    schedule_for,
)
from tpuframe.analysis.source_lint import (  # noqa: F401
    LintFinding,
    lint_paths,
    lint_source,
)
