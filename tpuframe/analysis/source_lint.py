"""Layer 3: AST lint over tpuframe source for known JAX footguns.

Each rule institutionalizes a defect class rounds 4-5 found by hand:

  TF101  host conversion on a traced value — ``float(x)``,
         ``np.asarray(x)``, ``x.item()`` inside a jitted/shard_mapped
         function forces a trace-time concretization error (or, worse,
         silently bakes a constant when the value happens to be static).
  TF102  Python control flow on a traced value — ``if jnp.any(mask):``
         inside traced code raises ConcretizationTypeError at trace
         time; the fix is ``lax.cond``/``jnp.where``.  Only tests that
         syntactically involve array computation (``jnp.``/``lax.``
         calls, ``.any()``/``.all()``) are flagged — ``if axes:`` on
         static config is fine and common.
  TF103  timing without a sync — a ``t1 - t0`` duration around a
         dispatched step measures *dispatch* (async!) unless something
         in the function forces completion (``block_until_ready``,
         ``device_get``, ``float()``/``.item()`` on the result).  The
         round-4 perf rigs hit exactly this.
  TF104  ``pallas_call`` without an explicit ``interpret=`` decision —
         the silent-interpret failure mode: a kernel that never went
         through Mosaic presenting itself as a TPU kernel.  Every call
         site must say how it decides
         (``ops.kernel_impl.interpret_default()``).
  TF105  resilience bypass — (a) a raw GCS client call
         (``download_as_bytes``/``upload_from_string``/``list_blobs``/
         ...) anywhere outside ``data/gcs.py``: every storage op must go
         through the retry-wrapped layer, or it silently loses backoff,
         timeouts, fault seams and retry metrics; (b) a ``while True:``
         loop that sleeps but never compares, raises, or reads a clock —
         an unbounded retry loop with no exit condition, the shape that
         wedges a supervisor forever (use RetryPolicy).
  TF107  ad-hoc step instrumentation in a hot path — a bare ``print()``
         or ``time.time()``/``perf_counter()`` timer inside per-step
         code (the train step in ``parallel/step.py``, the data
         pipeline in ``data/pipeline.py``) bypasses the structured
         event log: it costs host time every step, interleaves across
         hosts, and is invisible to the offline analyzer.  Route it
         through ``tpuframe.obs`` (``events.emit``/``metrics.bump`` —
         the host loop in train.py owns the one sanctioned timer).
         Also fires on ``print()`` inside *traced* code anywhere: a
         print under jit runs at trace time only, so it is not the
         instrumentation it looks like (use ``jax.debug.print``).
  TF108  bare rematerialization in model/step code — a direct
         ``jax.checkpoint``/``jax.remat``/``nn.remat`` call inside
         ``models/`` or ``parallel/`` bypasses the ``tpuframe.mem``
         policy registry (same registry-seam rule as TF105's GCS
         check): the remat decision becomes invisible to the offline
         policy search, the tuning DB and the run-event record.  Route
         modules through ``mem.remat_module`` and loss functions
         through ``mem.wrap`` / the step factories' ``remat_policy=``.
  TF109  un-bucketed compile in the serving path — a ``jax.jit``/
         ``pjit``/``pmap`` call or a raw ``model.apply`` anywhere in
         ``serve/`` except ``serve/engine.py`` (the one sanctioned
         compile seam).  The scheduler/loadgen layers run per request;
         a novel shape reaching the compiler there is a silent
         multi-second stall mid-serving — every serving program must
         come from the engine's bucketed AOT table.
  TF110  optimizer update outside the weight-update seam — a
         ``tx.update(...)``/``optax.apply_updates(...)`` call in
         ``parallel/`` or ``train.py`` outside ``parallel/step.py`` /
         ``parallel/zero1.py`` (the seam ``TPUFRAME_WEIGHT_UPDATE``
         switches) silently bypasses ZeRO-1 weight-update sharding:
         the stray site updates replicated params against sharded
         optimizer state, or re-materializes the full state the zero1
         layout exists to avoid.  ``parallel/hvd.py`` is seam-adjacent
         (it *composes* an ``optax.GradientTransformation``; step.py
         still applies it) and exempt.
  TF111  background thread outside the sanctioned modules — a
         ``threading.Thread`` created anywhere but ``ckpt/``,
         ``data/pipeline.py``, ``obs/heartbeat.py``, ``obs/timeline.py``
         (its device watcher waits and never launches) or ``launch/``.
         Background threads issuing collectives is the ordering hazard
         ``ckpt/checkpoint.py`` documents (a worker's collective
         interleaving with the main loop's compiled steps); the
         sanctioned modules are the ones audited to never do that.
         Threads that provably never touch jax suppress with a reason.
  TF116  world-size read cached at module import — a module-level
         ``N = jax.device_count()`` (or ``process_count``/
         ``local_device_count``/``process_index``) outside the
         sanctioned seams (``elastic/``, ``launch/``, ``parallel/``)
         snapshots the world before the run resolves it: under elastic
         resizing the world changes across relaunch attempts, and the
         import-time constant silently disagrees with the mesh the
         attempt actually built.  Resolve per run via
         ``tpuframe.elastic.current_world()``; provably-static uses
         suppress with a reason.
  TF106  compiler-env mutation that can run after jax backend init —
         ``os.environ["XLA_FLAGS"] = ...`` (or ``LIBTPU_INIT_ARGS``,
         via assignment/setdefault/update/putenv) is snapshotted by the
         backend at init and silently ignored afterwards: the exact
         footgun ``parallel/tuning.py:apply()`` can only catch at
         runtime with a warning.  Fires on any such write inside a
         function (functions run at arbitrary times) unless the
         function probes backend init first (references ``xla_bridge``
         or ``_backends``, tuning.apply's pattern), and on
         module-level writes placed *after* a module-level
         ``import jax``.  Per-compile ``compiler_options``
         (``TPUFRAME_XLA_OPTS`` / tpuframe.tune) is the safe carrier —
         it travels inside the compile request.
  TF114  lock discipline in the background-thread modules — inside the
         TF111-sanctioned modules that actually run worker threads
         (``ckpt/``, ``obs/exporter.py``, ``obs/flight.py``,
         ``data/pipeline.py``), shared state guarded by a lock must
         only be mutated under ``with <lock>:``.  The rule is opt-in
         by construction: a class that owns a ``threading.Lock``/
         ``RLock``/``Condition`` attribute (or a module that owns a
         module-level one) has declared its state shared, so every
         unlocked mutation of instance attributes (or lock-guarded
         module globals) is a statically visible race — the hammer
         PR 9 applied to the obs counters, made a checked invariant.
         Constructor bodies (``__init__``/``__post_init__``/
         ``__new__``) are happens-before publication and exempt;
         call-site-serialized lifecycle mutations suppress with
         ``# tf-lint: ok[TF114]`` and a reason.
  TF118  raw network client call outside the router/exporter seams — a
         ``urllib.request.urlopen``/``http.client.HTTPConnection``/
         ``socket.socket``/``socket.create_connection`` call anywhere
         but ``serve/router.py`` (the fleet's one HTTP client, where
         every request rides a RetryPolicy: decorrelated jitter, attempt
         timeout, deadline) or ``obs/exporter.py`` (the one server).  An
         ad-hoc client call elsewhere has no retry budget, no fault
         seams and no obs counters — the same bypass class as TF105's
         raw-GCS check, at the fleet seam.  Local non-fleet socket use
         (ephemeral-port probes) suppresses with ``# tf-lint: ok[TF118]``
         and a reason.
  TF119  raw mesh construction outside the mesh seam — a
         ``jax.sharding.Mesh(...)``/``jax.make_mesh(...)`` call anywhere
         but ``parallel/mesh.py`` (the one module that knows the axis
         order) or ``parallel/pspec.py`` (the declarative spec that
         lowers onto it).  A hand-built mesh silently re-decides the
         axis names and the ICI/DCN ordering that every replica-group
         validation, batch partition and DCN-split attribution keys on —
         the exact drift class the hierarchical ``slice`` axis makes
         fatal (an inner-out slice axis puts model traffic on DCN).
         Build through ``mesh.make_mesh(MeshSpec(...))`` or a parsed
         ``ParallelSpec``; degenerate single-purpose meshes (the
         process-axis host mesh, topology probes) suppress with
         ``# tf-lint: ok[TF119]`` and a reason.
  TF120  strategy registration outside the spec seam — a hand-built
         ``StrategyMeta(...)`` or a write into the ``STRATEGIES``
         registry (subscript assignment, ``.update(...)``,
         ``.setdefault(...)``) anywhere but ``analysis/strategies.py``.
         Since the grammar closed over all nine strategies, the one
         sanctioned way to add a strategy is
         ``register_spec_strategy("name", "spec", ...)`` — a hand-wired
         builder bypasses spec lowering, so its CommBudget/schedule
         record is no longer auto-derived from the grammar and the
         planner cannot enumerate it.  Out-of-repo experiment plugins
         suppress with ``# tf-lint: ok[TF120]`` and a reason.
  TF121  live weight mutation outside the sanctioned swap seam — an
         assignment to (or ``setattr`` of) a ``.params`` attribute in
         the rollout-bearing modules (``serve/rollout.py``,
         ``serve/replica.py``).  ``LMEngine.swap_params()`` is the ONE
         way live weights change: it validates tree structure and
         leaf shapes/dtypes against what the AOT table was compiled
         for, so the zero-recompile hot-swap floor holds by
         construction.  A raw ``engine.params = ...`` skips that check
         and can silently poison every compiled program; test fixtures
         suppress with ``# tf-lint: ok[TF121]`` and a reason.
  TF122  ``declared_overlapped=True`` signed outside the strategy seam —
         the keyword passed (truthy) to ``StrategyMeta(...)`` or
         ``register_spec_strategy(...)`` anywhere but
         ``analysis/strategies.py``.  The declaration is a live
         contract, not metadata: ``shardflow.detect_exposed_comm``
         turns from report-only into a hard gate for strategies that
         carry it, so signing it is reserved to the one module whose
         registrations the fixture/schedule pins actually cover.  A
         strategy signed elsewhere would flip the gate on a program
         nothing pins; seeded-positive test rigs suppress with
         ``# tf-lint: ok[TF122]`` and a reason.
  TF123  raw span event emitted outside the tracing seam — an
         ``events.emit("span_open"/"span_close"/"span_note", ...)``
         call anywhere but ``obs/tracing.py``.  Span records carry
         invariants the schema alone cannot express: every open must
         have a matching close (``obs anomalies`` reports leaks), ids
         come from the process-unique minting counter, and the
         open-span registry behind the ``tpuframe_open_spans`` gauge
         is only maintained by ``tracing.open_span``/``close_span``.
         A hand-rolled emit produces spans the verifier counts as
         leaked or orphaned; use ``tracing.open_span``/``close_span``/
         ``span``/``note``, or suppress with ``# tf-lint: ok[TF123]``
         and a reason (seeded-positive test rigs).
  TF124  raw cross-slice collective outside the hierarchical seam — a
         ``lax`` collective whose axis argument names the ``slice``
         mesh axis (the string literal) anywhere but
         ``parallel/hier.py``.  The slice axis is the DCN fabric:
         ``hier.py`` owns every collective that crosses it, because
         that is where the two-level lowering (in-slice reduce-scatter
         → 1/n cross-slice exchange → in-slice all-gather) is
         applied.  A raw ``lax.pmean(g, ("data", "slice"))`` elsewhere
         ships full-size traffic over DCN behind the seam's back —
         exactly the term the hierarchy exists to crush — and is
         invisible to the DCN byte budgets the comm-split auditor
         pins.  Collectives over computed axis variables are untouched
         (the seam's own helpers pass those); deliberate raw crossings
         (scalar control beacons) suppress with ``# tf-lint:
         ok[TF124]`` and a reason.

Scope: TF101/TF102 only fire *inside functions known to be traced*
(decorated with ``jax.jit``/``pmap``/``shard_map`` or passed to
``jax.jit(...)`` by name, plus their nested defs) — host code is
allowed, and encouraged, to call ``float()``.  TF103/TF104 are
function-/call-site-local and apply everywhere.

Suppression: append ``# tf-lint: ok[TF103]`` (or bare ``# tf-lint: ok``
for all rules) to the offending line or to the enclosing ``def`` line,
with a reason in a neighbouring comment.  Suppressions are grep-able
policy, the same contract as the VMEM known-exclusion registry.

Structure: the shared scaffolding — suppression-comment parsing,
path-scope flags, the traced-function walk, finding emission — lives in
:class:`FileContext` plus three registries (``_NODE_RULES`` run on every
non-def node with the enclosing function's traced-ness, ``_FN_RULES``
once per function, ``_FILE_RULES`` once per file).  A new rule is one
registered function reading ``ctx``/``node``/``fn`` — it never copies
the walk or the suppression plumbing (TF114 below is the template).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path

RULES = {
    "TF101": "host conversion on a traced value inside traced code",
    "TF102": "Python control flow on a traced (array) value",
    "TF103": "duration measured around device work without a sync",
    "TF104": "pallas_call without an explicit interpret= decision",
    "TF105": "storage call or retry loop bypassing the resilience layer",
    "TF106": "compiler-env (XLA_FLAGS/LIBTPU_INIT_ARGS) mutation that can "
             "run after jax backend init",
    "TF107": "print()/time.time() step instrumentation in a hot path "
             "bypassing tpuframe.obs",
    "TF108": "bare jax.checkpoint/jax.remat/nn.remat in model/step code "
             "bypassing the tpuframe.mem policy registry",
    "TF109": "jit/apply in the serving path outside the engine's "
             "bucketed AOT table (serve/engine.py)",
    "TF110": "optimizer update (tx.update/optax.apply_updates) outside "
             "the weight-update seam (parallel/step.py, parallel/zero1.py)",
    "TF111": "threading.Thread created outside the sanctioned background-"
             "work modules (ckpt/, data/pipeline.py, obs/heartbeat.py, "
             "launch/)",
    "TF112": "events.emit() with an event type not registered in "
             "obs/events.py's REQUIRED_FIELDS schema contract",
    "TF113": "http.server used outside the sanctioned telemetry endpoint "
             "(obs/exporter.py)",
    "TF114": "lock-guarded shared state mutated outside `with <lock>:` in "
             "a background-thread module (ckpt/, obs/exporter.py, "
             "obs/flight.py, data/pipeline.py)",
    "TF116": "world-size read (jax.process_count/device_count/"
             "local_device_count/process_index) cached at module import "
             "outside the elastic/launch/parallel seams — stale after an "
             "elastic resize",
    "TF117": "jax.block_until_ready()/jax.device_get() inside a traced "
             "hot path (parallel/, serve/engine.py) — forces a schedule "
             "barrier that destroys collective/compute overlap",
    "TF118": "raw network client call (urllib.request.urlopen/"
             "http.client/socket.socket) outside the sanctioned fleet "
             "seams (serve/router.py, obs/exporter.py) — bypasses the "
             "RetryPolicy transport",
    "TF119": "raw mesh construction (jax.sharding.Mesh/jax.make_mesh) "
             "outside the mesh seam (parallel/mesh.py, "
             "parallel/pspec.py) — re-decides axis names and ICI/DCN "
             "ordering behind the spec grammar's back",
    "TF120": "strategy registration (StrategyMeta(...)/STRATEGIES "
             "write) outside analysis/strategies.py's "
             "register_spec_strategy seam — a hand-wired builder "
             "bypasses spec lowering and the planner's enumeration",
    "TF121": "live weight mutation (.params assignment / setattr) in "
             "the rollout modules (serve/rollout.py, serve/replica.py) "
             "outside the engine.swap_params() seam — skips the "
             "tree/shape/dtype validation that keeps hot swaps "
             "recompile-free",
    "TF122": "declared_overlapped=True signed outside "
             "analysis/strategies.py — the overlap declaration arms "
             "shardflow's exposed-comm hard gate, and only the strategy "
             "seam's registrations are covered by the pinned "
             "fixtures/schedules",
    "TF123": "raw span event (span_open/span_close/span_note) emitted "
             "outside obs/tracing.py — bypasses span-id minting and "
             "the open-span registry, producing spans the trace "
             "verifier counts as leaked or orphaned; use the "
             "tracing.open_span/close_span/span/note API",
    "TF124": "raw cross-slice collective (a lax collective naming the "
             "'slice' axis) outside the hierarchical seam "
             "(parallel/hier.py) — ships full-size traffic over DCN "
             "behind the two-level lowering and the per-fabric wire "
             "format, invisible to the pinned DCN byte budgets",
}

# TF107: per-step code — every call here runs once per step/batch, so
# ad-hoc prints and timers belong in obs.events/obs.metrics instead.
_HOT_PATH_SUFFIXES = ("parallel/step.py", "data/pipeline.py")

# TF107: clock reads that look like hand-rolled step timing.
_CLOCK_CALLS = {"time.time", "time.perf_counter", "time.monotonic"}

# TF106: env keys the backend snapshots at init — a later write is dead.
_COMPILER_ENV_KEYS = {"XLA_FLAGS", "LIBTPU_INIT_ARGS"}

# TF108: model/step code where every remat decision must route through
# tpuframe.mem; the registry itself is the one sanctioned call site.
_REMAT_SCOPE_PARTS = ("models/", "parallel/")
_REMAT_EXEMPT_PARTS = ("mem/",)
_BARE_REMAT_CALLEES = {
    "jax.checkpoint", "jax.remat", "nn.remat", "flax.linen.remat",
    "linen.remat", "jax.ad_checkpoint.checkpoint",
    "ad_checkpoint.checkpoint",
}

# TF109: the serving path above the compile seam — request-rate code
# where an unplanned compile is a user-visible stall.  engine.py owns
# the bucketed AOT table and is the one sanctioned call site.
_SERVE_SCOPE_PART = "serve/"
_SERVE_EXEMPT_SUFFIX = "serve/engine.py"
_SERVE_COMPILE_TAILS = {"jit", "pjit", "pmap"}

# TF110: the weight-update seam.  Optimizer math in parallel/ or
# train.py must go through step.py's _reduce_and_apply (which dispatches
# on TPUFRAME_WEIGHT_UPDATE) or zero1.py's sharded_update; hvd.py only
# composes a GradientTransformation (step.py applies it) and is exempt.
_WU_SCOPE_PART = "parallel/"
_WU_SCOPE_SUFFIX = "train.py"
_WU_EXEMPT_SUFFIXES = ("parallel/step.py", "parallel/zero1.py",
                       "parallel/hvd.py")
# Receivers whose ``.update(grads, state, ...)`` is optimizer math rather
# than a dict/metric update — the optax transformation naming convention.
_WU_OPTIMIZER_RECEIVERS = {"tx", "optimizer", "opt", "inner_tx"}

# TF111: modules sanctioned to spawn background threads.  Everywhere
# else a thread is the collective-ordering hazard checkpoint.py
# documents: a background thread issuing (or transitively triggering)
# collectives interleaves with the main loop's compiled steps, and the
# sanctioned modules are exactly the ones audited to never do that
# (ckpt's worker polls sidecar files instead of a barrier; the prefetch
# thread only device_puts; heartbeat only reads a counter; launch runs
# before any backend exists; the timeline's device watcher only waits on
# results the caller launched, and never launches).
_THREAD_SANCTIONED_PARTS = ("ckpt/", "data/pipeline.py",
                            "obs/heartbeat.py", "obs/timeline.py",
                            "launch/")

# TF112: receivers whose ``.emit("type", ...)`` is the structured event
# log — the in-tree import aliases for ``tpuframe.obs.events``.  A string
# literal first argument must name a type registered in REQUIRED_FIELDS,
# or the record fails schema validation at read time (the selfcheck
# gate); this catches it at lint time instead.  Computed first arguments
# are skipped (the registry can't resolve them statically).
_EMIT_RECEIVERS = {"events", "events_lib", "obs_events"}

# TF113: the one module allowed to stand up an HTTP endpoint.  Ad-hoc
# http.server use anywhere else forks the telemetry plane: unauthenticated
# sockets with no OpenMetrics contract, invisible to the exporter's
# health/port knobs.
_HTTP_EXEMPT_SUFFIX = "obs/exporter.py"

# TF114: the modules whose threads actually share mutable host state —
# the subset of the TF111-sanctioned list with a writer thread (ckpt's
# async save worker, the exporter's HTTP server thread, the flight
# recorder's dump-on-crash path, the pipeline's prefetch producer).
_LOCK_DISCIPLINE_PARTS = ("ckpt/", "obs/exporter.py", "obs/flight.py",
                          "data/pipeline.py")

# TF114: lock-type constructors whose assignment declares shared state,
# and container methods that mutate their receiver in place.
_LOCK_CTOR_TAILS = {"Lock", "RLock", "Condition"}
_MUTATING_METHODS = {
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "add", "discard", "popitem", "setdefault", "appendleft", "popleft",
}
_CTOR_METHODS = {"__init__", "__post_init__", "__new__"}

# TF116: the seams sanctioned to read the world size directly — the
# elastic resolver itself, the launcher (sizes the cluster before jax
# exists in the children) and parallel/ (mesh construction).  Everywhere
# else a module-import-time world read is a constant baked before the
# attempt resolved its world: under elastic resizing (TPUFRAME_ELASTIC)
# the device count changes across relaunch attempts, so the cache
# silently disagrees with the mesh the run actually built.  Per-run code
# goes through ``tpuframe.elastic.current_world()``.
_WORLD_SANCTIONED_PARTS = ("elastic/", "launch/", "parallel/")
_WORLD_READ_TAILS = {"process_count", "device_count",
                     "local_device_count", "process_index"}

# TF117: the overlap-critical hot paths — the strategy step programs
# (parallel/) and the serving engine.  A host sync inside TRACED code
# there pins a schedule barrier into every compiled step: the collective
# scheduler cannot move work across it, so the exposed-communication
# windows the schedule auditor polices reappear at the source level.
# Host-side synchronization (checkpoint flush, benchmark harness) is
# untraced and untouched.
_SYNC_SCOPE_PART = "parallel/"
_SYNC_SCOPE_SUFFIX = "serve/engine.py"
_SYNC_BARRIER_TAILS = {"block_until_ready", "device_get"}

# TF118: the fleet's network client seams.  router.py owns the one HTTP
# client (http_transport, always called under a RetryPolicy) and
# exporter.py the one server; a raw client call anywhere else skips
# retries, fault seams and the dispatch/scrape obs counters — the TF105
# raw-GCS bypass class at the fleet boundary.  ``socket.gethostname``
# and friends are not client calls and are untouched; local ephemeral-
# port probes suppress with a reason.
_NET_EXEMPT_SUFFIXES = ("serve/router.py", "obs/exporter.py")

# TF119: the mesh seam.  mesh.py owns axis names/order (slice axis
# OUTERMOST so cross-slice collectives ride DCN); pspec.py is the
# declarative grammar that lowers onto it.  Everything else builds
# through them.
_MESH_EXEMPT_SUFFIXES = ("parallel/mesh.py", "parallel/pspec.py")

# TF120: the strategy seam.  strategies.py owns the registry; every
# entry goes through register_spec_strategy so its budget/schedule
# record derives from the spec grammar and `tune plan` can enumerate it.
_STRATEGY_EXEMPT_SUFFIXES = ("analysis/strategies.py",)

# TF121: the live weight-swap seam.  engine.py hosts swap_params() (the
# validating setter); the rollout-bearing modules above it must never
# rebind a ``.params`` attribute directly — that is exactly the bypass
# that turns a checkpoint from the wrong model into a silent poisoning
# of every compiled program.
_SWAP_SCOPE_SUFFIXES = ("serve/rollout.py", "serve/replica.py")

# TF123: the one module allowed to emit raw span records.  The literals
# mirror obs/tracing.py's SPAN_EVENT_TYPES — no import (same
# importable-anywhere constraint as _event_type_registry below), and
# trace.check() cross-pins the two copies via the schema registry.
_TRACE_SEAM_SUFFIXES = ("obs/tracing.py",)
_SPAN_EVENT_LITERALS = ("span_open", "span_close", "span_note")

# TF124: the hierarchical-collective seam.  hier.py owns every
# collective that names the ``slice`` (DCN) axis — the two-level
# lowering lives there; pmean IS in the tails because a raw
# cross-slice pmean is precisely the full-size DCN transfer the seam
# exists to shrink.  Only the string literal ``"slice"`` is matched:
# computed axis tuples are how the seam's callers hand their axes down,
# and those stay untouched.
_HIER_SEAM_SUFFIXES = ("parallel/hier.py",)
_HIER_COLLECTIVE_TAILS = {
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather",
    "psum_scatter", "all_to_all",
}

_NET_CALL_DOTTED = {"socket.socket", "socket.create_connection"}
_NET_CALL_TAILS = {"urlopen", "HTTPConnection", "HTTPSConnection"}

# TF105a: google.cloud.storage blob/bucket methods — allowed only inside
# the retry-wrapped data/gcs.py layer.
_RAW_GCS_METHODS = {
    "download_as_bytes", "download_as_string", "download_to_filename",
    "upload_from_string", "upload_from_file", "upload_from_filename",
    "list_blobs", "rename_blob",
}

# Decorators that make a function body traced code.
_TRACING_DECORATORS = {"jit", "pmap", "pjit", "shard_map", "vmap"}

# Call-expression shapes treated as host conversions (TF101).
_HOST_CONVERTERS = {"float", "int", "bool", "complex"}
_NP_CONVERTERS = {"asarray", "array"}
_METHOD_CONVERTERS = {"item", "tolist"}

# TF103: callee names that look like dispatched device work...
_DEVICE_WORK_RE = re.compile(
    r"(step|apply|update|forward|jit|compile|sample|generate)", re.I)
# ...and callee/attribute names that force completion.
_SYNC_MARKERS = {"block_until_ready", "device_get", "item", "tolist",
                 "asarray", "array", "float"}

_SUPPRESS_RE = re.compile(r"#\s*tf-lint:\s*ok(?:\[([A-Z0-9, ]+)\])?")


_EVENT_REGISTRY_CACHE: frozenset | None = None


def _event_type_registry() -> frozenset:
    """Event types registered in ``obs/events.py``'s REQUIRED_FIELDS,
    extracted by AST parse — NOT by import: importing ``tpuframe.obs``
    pulls jax, and ``--lint-only`` must stay importable-anywhere.  An
    unreadable/refactored events.py yields an empty set, which makes
    TF112 inert rather than noisy."""
    global _EVENT_REGISTRY_CACHE
    if _EVENT_REGISTRY_CACHE is not None:
        return _EVENT_REGISTRY_CACHE
    types: frozenset = frozenset()
    try:
        src = (Path(__file__).resolve().parent.parent / "obs"
               / "events.py").read_text()
        tree = ast.parse(src)
    except (OSError, SyntaxError):
        tree = None
    if tree is not None:
        for node in ast.walk(tree):
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
            elif isinstance(node, ast.AnnAssign):
                target = node.target
            if (isinstance(target, ast.Name)
                    and target.id == "REQUIRED_FIELDS"
                    and isinstance(node.value, ast.Dict)):
                types = frozenset(k.value for k in node.value.keys
                                  if isinstance(k, ast.Constant))
                break
    _EVENT_REGISTRY_CACHE = types
    return types


@dataclass
class LintFinding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute(Name('jax'),'jit'); '' when not a name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_tracing_decorator(dec: ast.AST) -> bool:
    # @jax.jit / @jit / @shard_map ...
    tail = _dotted(dec).rsplit(".", 1)[-1]
    if tail in _TRACING_DECORATORS:
        return True
    if isinstance(dec, ast.Call):
        # @partial(jax.jit, ...) / @jax.jit(...) / @shard_map(...)
        if _is_tracing_decorator(dec.func):
            return True
        if _dotted(dec.func).rsplit(".", 1)[-1] == "partial" and dec.args:
            return _is_tracing_decorator(dec.args[0])
    return False


def _jitted_names(tree: ast.Module) -> set[str]:
    """Function names passed to jax.jit(...)/jit(...) anywhere."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func).rsplit(".", 1)[-1]
        if callee not in _TRACING_DECORATORS:
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif (isinstance(arg, ast.Call)
                  and _dotted(arg.func).rsplit(".", 1)[-1] == "partial"
                  and arg.args and isinstance(arg.args[0], ast.Name)):
                names.add(arg.args[0].id)
    return names


def _test_touches_arrays(test: ast.AST) -> bool:
    """True when an `if` test syntactically involves array computation."""
    for node in ast.walk(test):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d.startswith(("jnp.", "lax.", "jax.numpy.", "jax.lax.")):
                return True
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("any", "all")
                    and not _dotted(node.func).startswith(("np.", "numpy."))):
                return True
    return False


class _FnInfo:
    def __init__(self, node, traced: bool, probes_backend: bool = False):
        self.node = node
        self.traced = traced
        self.probes_backend = probes_backend


def _probes_backend(fn_node) -> bool:
    """TF106 exemption: the function checks whether the backend already
    initialized (``jax._src.xla_bridge._backends`` — tuning.apply's
    pattern) or replaces the process outright (``os.execvpe``: the next
    process re-initializes from the new env)."""
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("_backends",
                                                           "xla_bridge"):
            return True
        if isinstance(sub, ast.Name) and sub.id == "xla_bridge":
            return True
        if (isinstance(sub, ast.Call) and _dotted(sub.func)
                .rsplit(".", 1)[-1] in ("execv", "execve", "execvp",
                                        "execvpe")):
            return True
    return False


def _iter_local(node):
    """Child nodes of ``node`` excluding nested function subtrees (each
    nested def is checked in its own visit with its own traced-ness)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield child
        yield from _iter_local(child)


def _nested_defs(node):
    out = []

    def rec(n):
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(child)
            else:
                rec(child)

    rec(node)
    return out


class FileContext:
    """Everything one lint pass shares across rules: the parsed tree,
    the raw lines (suppression comments live there), the path-derived
    scope flags, and the emit/suppression plumbing.  Rules receive this
    instead of re-deriving any of it."""

    def __init__(self, tree: ast.Module, src: str, path: str):
        self.tree = tree
        self.lines = src.splitlines()
        self.path = path
        norm = path.replace("\\", "/")
        self.norm_path = norm
        self.findings: list[LintFinding] = []
        self.jitted = _jitted_names(tree)
        self.hot_path = norm.endswith(_HOT_PATH_SUFFIXES)
        self.remat_scope = (any(p in norm for p in _REMAT_SCOPE_PARTS)
                            and not any(p in norm
                                        for p in _REMAT_EXEMPT_PARTS))
        self.serve_scope = (_SERVE_SCOPE_PART in norm
                            and not norm.endswith(_SERVE_EXEMPT_SUFFIX))
        self.wu_scope = ((_WU_SCOPE_PART in norm
                          or norm.endswith(_WU_SCOPE_SUFFIX))
                         and not norm.endswith(_WU_EXEMPT_SUFFIXES))
        self.thread_scope = not any(p in norm
                                    for p in _THREAD_SANCTIONED_PARTS)
        self.http_scope = not norm.endswith(_HTTP_EXEMPT_SUFFIX)
        self.net_scope = not norm.endswith(_NET_EXEMPT_SUFFIXES)
        self.mesh_scope = not norm.endswith(_MESH_EXEMPT_SUFFIXES)
        self.strategy_scope = not norm.endswith(
            _STRATEGY_EXEMPT_SUFFIXES)
        self.swap_scope = norm.endswith(_SWAP_SCOPE_SUFFIXES)
        self.trace_scope = not norm.endswith(_TRACE_SEAM_SUFFIXES)
        self.lock_scope = any(p in norm for p in _LOCK_DISCIPLINE_PARTS)
        self.hier_scope = not norm.endswith(_HIER_SEAM_SUFFIXES)
        self.world_scope = not any(p in norm
                                   for p in _WORLD_SANCTIONED_PARTS)
        self.sync_scope = (_SYNC_SCOPE_PART in norm
                           or norm.endswith(_SYNC_SCOPE_SUFFIX))
        # TF106: a module-level compiler-env write is safe only BEFORE
        # the module-level jax import (the conftest/bootstrap pattern).
        self.jax_import_line = None
        for top in tree.body:
            if isinstance(top, ast.Import) and any(
                    a.name == "jax" or a.name.startswith("jax.")
                    for a in top.names):
                self.jax_import_line = top.lineno
                break
            if isinstance(top, ast.ImportFrom) and top.module and (
                    top.module == "jax"
                    or top.module.startswith("jax.")):
                self.jax_import_line = top.lineno
                break

    def suppressed(self, rule: str, *linenos: int) -> bool:
        for ln in linenos:
            if not (1 <= ln <= len(self.lines)):
                continue
            m = _SUPPRESS_RE.search(self.lines[ln - 1])
            if m and (m.group(1) is None
                      or rule in re.split(r"[,\s]+", m.group(1))):
                return True
        return False

    def emit(self, rule: str, node: ast.AST, msg: str,
             fn: _FnInfo | None = None) -> None:
        def_line = fn.node.lineno if fn is not None else node.lineno
        if not self.suppressed(rule, node.lineno, def_line):
            self.findings.append(
                LintFinding(rule, self.path, node.lineno, msg))


# ---------------------------------------------------------------------------
# Rule registries.  _NODE_RULES run on every non-def node (module level
# with fn=None, then once per enclosing function with its _FnInfo);
# _FN_RULES once per function def; _FILE_RULES once per file, last.
# Registration order is emission order — tests pin it.
# ---------------------------------------------------------------------------

_NODE_RULES: list = []
_FN_RULES: list = []
_FILE_RULES: list = []


def _node_rule(fn):
    _NODE_RULES.append(fn)
    return fn


def _fn_rule(fn):
    _FN_RULES.append(fn)
    return fn


def _file_rule(fn):
    _FILE_RULES.append(fn)
    return fn


@_node_rule
def _tf113_http_server(ctx: FileContext, node, fn):
    if not ctx.http_scope:
        return
    if isinstance(node, (ast.Import, ast.ImportFrom)):
        modules = ([a.name for a in node.names]
                   if isinstance(node, ast.Import)
                   else [node.module or ""])
        if any(m == "http.server" or m.startswith("http.server.")
               for m in modules):
            ctx.emit("TF113", node,
                     "http.server imported outside obs/exporter.py — the "
                     "exporter is the one sanctioned HTTP endpoint "
                     "(OpenMetrics contract, health probe, port knobs); "
                     "register gauges/collectors on it instead of "
                     "standing up another server", fn)
    if (isinstance(node, ast.Attribute)
            and _dotted(node) == "http.server"):
        ctx.emit("TF113", node,
                 "http.server used outside obs/exporter.py — route the "
                 "endpoint through the telemetry exporter", fn)


@_node_rule
def _tf118_raw_network(ctx: FileContext, node, fn):
    if not ctx.net_scope or not isinstance(node, ast.Call):
        return
    dotted = _dotted(node.func)
    if not dotted:
        return
    tail = dotted.rsplit(".", 1)[-1]
    if dotted in _NET_CALL_DOTTED or dotted in _NET_CALL_TAILS or (
            tail in _NET_CALL_TAILS
            and dotted.startswith(("urllib.", "http.client.",
                                   "request.", "client."))):
        ctx.emit("TF118", node,
                 f"raw network client call {dotted}() outside "
                 f"serve/router.py / obs/exporter.py — fleet traffic must "
                 f"ride router.http_transport under a RetryPolicy "
                 f"(backoff, attempt timeout, deadline, obs counters); "
                 f"local non-fleet socket use suppresses with a reason",
                 fn)


def _tf106_emit(ctx: FileContext, node, key, fn):
    if fn is not None:
        if fn.probes_backend:
            return  # checked backend init / re-execs — tuning.apply
    elif (ctx.jax_import_line is None
          or node.lineno < ctx.jax_import_line):
        return  # module-level write before the jax import: safe
    ctx.emit("TF106", node,
             f"os.environ[{key!r}] written where the jax backend may "
             f"already be initialized — the backend snapshots compiler "
             f"env at init and later writes are silently dead; pass "
             f"per-compile compiler_options (TPUFRAME_XLA_OPTS / "
             f"tpuframe.tune) or probe xla_bridge._backends first", fn)


@_node_rule
def _tf106_compiler_env(ctx: FileContext, node, fn):
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            if (isinstance(t, ast.Subscript)
                    and _dotted(t.value) == "os.environ"
                    and isinstance(t.slice, ast.Constant)
                    and t.slice.value in _COMPILER_ENV_KEYS):
                _tf106_emit(ctx, node, t.slice.value, fn)
    if isinstance(node, ast.Call):
        callee = _dotted(node.func)
        if (callee in ("os.environ.setdefault", "os.putenv")
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value in _COMPILER_ENV_KEYS):
            _tf106_emit(ctx, node, node.args[0].value, fn)
        elif callee == "os.environ.update":
            keys = [kw.arg for kw in node.keywords
                    if kw.arg in _COMPILER_ENV_KEYS]
            for a in node.args:
                if isinstance(a, ast.Dict):
                    keys += [k.value for k in a.keys
                             if isinstance(k, ast.Constant)
                             and k.value in _COMPILER_ENV_KEYS]
            for key in keys:
                _tf106_emit(ctx, node, key, fn)


@_node_rule
def _tf_call_rules(ctx: FileContext, node, fn):
    """The per-call rules (TF101/104/105a/107/108/109/110/111/112), in
    the historical emission order for any single call node."""
    if not isinstance(node, ast.Call):
        return
    traced = fn is not None and fn.traced
    callee = _dotted(node.func)
    tail = callee.rsplit(".", 1)[-1]
    if traced:
        if (tail in _HOST_CONVERTERS and callee == tail
                and node.args
                and not isinstance(node.args[0], ast.Constant)):
            ctx.emit("TF101", node,
                     f"{tail}() on a possibly-traced value inside "
                     f"traced code — concretizes at trace time", fn)
        elif (callee.startswith(("np.", "numpy.", "onp."))
              and tail in _NP_CONVERTERS):
            ctx.emit("TF101", node,
                     f"{callee}() pulls a traced value to host — "
                     f"use jnp inside traced code", fn)
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _METHOD_CONVERTERS
              and not callee.startswith(("np.", "numpy."))):
            ctx.emit("TF101", node,
                     f".{node.func.attr}() on a possibly-traced "
                     f"value inside traced code", fn)
    if tail == "pallas_call" and not any(
            kw.arg == "interpret" for kw in node.keywords):
        ctx.emit("TF104", node,
                 "pallas_call without interpret= — decide "
                 "Mosaic-vs-interpret explicitly "
                 "(ops.kernel_impl.interpret_default())",
                 fn)
    if ctx.serve_scope and (
            tail in _SERVE_COMPILE_TAILS
            or (isinstance(node.func, ast.Attribute)
                and node.func.attr == "apply")):
        what = (f"{callee}()" if tail in _SERVE_COMPILE_TAILS
                else f".apply()")
        ctx.emit("TF109", node,
                 f"{what} in the serving path above the compile seam "
                 f"— every serving program must come from "
                 f"serve/engine.py's bucketed AOT table (an "
                 f"un-bucketed shape compiling mid-serving is a "
                 f"multi-second stall)", fn)
    if ctx.wu_scope and (
            callee in ("optax.apply_updates", "apply_updates")
            or (isinstance(node.func, ast.Attribute)
                and node.func.attr == "update"
                and _dotted(node.func.value).rsplit(".", 1)[-1]
                in _WU_OPTIMIZER_RECEIVERS
                and len(node.args) >= 2)):
        ctx.emit("TF110", node,
                 f"{callee}() optimizer update outside the "
                 f"weight-update seam — route it through "
                 f"parallel/step.py's _reduce_and_apply (or "
                 f"parallel/zero1.py's sharded_update) so "
                 f"TPUFRAME_WEIGHT_UPDATE=zero1 still shards the "
                 f"update and optimizer state", fn)
    if (ctx.thread_scope
            and callee in ("threading.Thread", "Thread")):
        ctx.emit("TF111", node,
                 f"{callee}() outside the sanctioned background-work "
                 f"modules (ckpt/, data/pipeline.py, "
                 f"obs/heartbeat.py, obs/timeline.py, launch/) — a "
                 f"background thread "
                 f"that issues collectives interleaves with the main "
                 f"loop's compiled steps (the ordering hazard "
                 f"ckpt/checkpoint.py documents); if the thread "
                 f"provably never touches jax, suppress with "
                 f"tf-lint: ok[TF111] and a reason", fn)
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr == "emit"
            and _dotted(node.func.value).rsplit(".", 1)[-1]
            in _EMIT_RECEIVERS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)):
        registry = _event_type_registry()
        if registry and node.args[0].value not in registry:
            ctx.emit("TF112", node,
                     f"events.emit({node.args[0].value!r}) — type not "
                     f"registered in obs/events.py REQUIRED_FIELDS; "
                     f"unregistered types fail schema validation at "
                     f"read time (the selfcheck CI gate), so register "
                     f"the type (with its required fields) first", fn)
    if ctx.remat_scope and callee in _BARE_REMAT_CALLEES:
        ctx.emit("TF108", node,
                 f"{callee}() bare rematerialization in model/step "
                 f"code bypasses the tpuframe.mem policy registry — "
                 f"use mem.remat_module for modules, mem.wrap / the "
                 f"step factories' remat_policy= for loss functions",
                 fn)
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr in _RAW_GCS_METHODS
            and not ctx.norm_path.endswith("data/gcs.py")):
        ctx.emit("TF105", node,
                 f".{node.func.attr}() raw GCS client call outside "
                 f"data/gcs.py — route it through the retry-wrapped "
                 f"gcs layer (tpuframe.resilience)", fn)
    if callee == "print":
        if traced:
            ctx.emit("TF107", node,
                     "print() inside traced code runs at trace time "
                     "only, not per step — use jax.debug.print, or "
                     "emit from the host loop via tpuframe.obs", fn)
        elif ctx.hot_path and fn is not None:
            ctx.emit("TF107", node,
                     "print() in per-step hot-path code bypasses the "
                     "structured event log — use tpuframe.obs "
                     "(events.emit / metrics.bump)", fn)
    elif ctx.hot_path and fn is not None and callee in _CLOCK_CALLS:
        ctx.emit("TF107", node,
                 f"{callee}() hand-rolled step timing in a hot path "
                 f"— the train loop's goodput meter owns step "
                 f"timing; route measurements through tpuframe.obs",
                 fn)


def _tf105_unbounded_retry(ctx: FileContext, node: ast.While, fn):
    """TF105b: ``while True`` + sleep with no comparison, raise, or
    clock read in the loop's own body is a retry loop that can never
    give up — it outlives deadlines, watchdogs and operators."""
    sleeps = False
    bounded = False
    for child in node.body:
        for sub in [child, *_iter_local(child)]:
            if isinstance(sub, (ast.Compare, ast.Raise)):
                bounded = True
            elif isinstance(sub, ast.Call):
                tail = _dotted(sub.func).rsplit(".", 1)[-1]
                if tail == "sleep":
                    sleeps = True
                elif tail in ("time", "monotonic", "perf_counter"):
                    bounded = True
    if sleeps and not bounded:
        ctx.emit("TF105", node,
                 "unbounded `while True` retry loop: sleeps but never "
                 "compares, raises, or reads a clock — use "
                 "resilience.RetryPolicy (bounded attempts + deadline)",
                 fn)


@_node_rule
def _tf102_control_flow(ctx: FileContext, node, fn):
    traced = fn is not None and fn.traced
    if isinstance(node, ast.While):
        if (isinstance(node.test, ast.Constant)
                and node.test.value is True):
            _tf105_unbounded_retry(ctx, node, fn)
        if traced and _test_touches_arrays(node.test):
            ctx.emit("TF102", node,
                     "Python branch on an array-valued test inside "
                     "traced code — use lax.cond/jnp.where", fn)
    elif traced and isinstance(node, (ast.If, ast.IfExp)):
        if _test_touches_arrays(node.test):
            ctx.emit("TF102", node,
                     "Python branch on an array-valued test inside "
                     "traced code — use lax.cond/jnp.where", fn)


@_node_rule
def _tf124_slice_seam(ctx: FileContext, node, fn):
    """A lax collective whose arguments contain the string literal
    ``"slice"`` — the DCN mesh axis — outside parallel/hier.py.  The
    literal-only match is deliberate: the seam's callers (step.py,
    zero1.py) pass computed axis tuples resolved from the mesh, so a
    bare ``"slice"`` in a collective call is someone hand-routing
    traffic across the DCN fabric."""
    if not ctx.hier_scope or not isinstance(node, ast.Call):
        return
    callee = _dotted(node.func)
    if not callee.startswith(("lax.", "jax.lax.")):
        return
    if callee.rsplit(".", 1)[-1] not in _HIER_COLLECTIVE_TAILS:
        return
    for arg in list(node.args) + [kw.value for kw in node.keywords]:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Constant) and sub.value == "slice":
                ctx.emit("TF124", node,
                         f"raw cross-slice `{callee}` names the 'slice' "
                         f"(DCN) axis outside parallel/hier.py — route "
                         f"through hier.hier_mean/scatter_mean/gather so "
                         f"the two-level lowering and the DCN wire "
                         f"format apply, or suppress with tf-lint: "
                         f"ok[TF124] and a reason", fn)
                return


@_node_rule
def _tf116_cached_world(ctx: FileContext, node, fn):
    """Module-level (fn is None) assignment whose value reads the world
    size from jax.  Reads inside functions are fine — they run when the
    attempt does, after the world is resolved."""
    if fn is not None or not ctx.world_scope:
        return
    if not isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
        return
    if node.value is None:
        return
    for sub in ast.walk(node.value):
        if not isinstance(sub, ast.Call):
            continue
        callee = _dotted(sub.func)
        tail = callee.rsplit(".", 1)[-1]
        if tail in _WORLD_READ_TAILS and callee == f"jax.{tail}":
            ctx.emit("TF116", node,
                     f"{callee}() cached in a module-level binding — "
                     f"the value is snapshotted at import, before the "
                     f"attempt resolves its world, and goes stale when "
                     f"an elastic resize (TPUFRAME_ELASTIC) changes the "
                     f"device count across relaunches; resolve per run "
                     f"via tpuframe.elastic.current_world(), or "
                     f"suppress with tf-lint: ok[TF116] and a reason "
                     f"if the binding is provably world-invariant", fn)
            return


@_node_rule
def _tf117_traced_sync(ctx: FileContext, node, fn):
    """A host synchronization point inside code that is itself traced:
    ``jax.block_until_ready`` / ``.block_until_ready()`` /
    ``jax.device_get`` under a jit/pmap/shard_map decorator in the
    overlap-critical paths.  Untraced host functions (checkpoint sync,
    bench harnesses) are exactly where these calls belong and are not
    in scope."""
    if not ctx.sync_scope or fn is None or not fn.traced:
        return
    if not isinstance(node, ast.Call):
        return
    callee = _dotted(node.func)
    if callee.rsplit(".", 1)[-1] in _SYNC_BARRIER_TAILS:
        ctx.emit("TF117", node,
                 f"`{callee}()` inside traced hot-path code forces a "
                 f"schedule barrier — the compiled step stalls until "
                 f"every in-flight collective drains, so nothing can "
                 f"overlap across this point; sync on the host after "
                 f"the step returns, or suppress with tf-lint: "
                 f"ok[TF117] and a reason", fn)


@_node_rule
def _tf119_raw_mesh(ctx: FileContext, node, fn):
    """A mesh constructed by hand outside the mesh seam:
    ``Mesh(...)`` in any dotted spelling, or jax's own
    ``make_mesh(...)`` (``jax.make_mesh``/``jax.sharding.make_mesh`` —
    NOT ``mesh_lib.make_mesh``, which IS the seam).  Axis names and the
    outermost-slice ordering are the contract every downstream consumer
    keys on (replica-group validation, ``batch_axes``, the ICI/DCN
    byte split); a raw construction opts out of all of it silently."""
    if not ctx.mesh_scope or not isinstance(node, ast.Call):
        return
    callee = _dotted(node.func)
    tail = callee.rsplit(".", 1)[-1]
    raw = (tail == "Mesh"
           or (tail == "make_mesh"
               and callee in ("jax.make_mesh", "jax.sharding.make_mesh",
                              "sharding.make_mesh")))
    if raw:
        ctx.emit("TF119", node,
                 f"raw `{callee}(...)` outside parallel/mesh.py — a "
                 f"hand-built mesh re-decides axis names and the "
                 f"ICI/DCN slice ordering behind the spec grammar's "
                 f"back; build through mesh.make_mesh(MeshSpec(...)) / "
                 f"ParallelSpec.make_mesh(), or suppress with tf-lint: "
                 f"ok[TF119] and a reason", fn)


@_node_rule
def _tf120_strategy_seam(ctx: FileContext, node, fn):
    """A strategy registered behind the spec seam's back: a hand-built
    ``StrategyMeta(...)`` or any write into the ``STRATEGIES`` registry
    (``STRATEGIES[name] = ...``, ``STRATEGIES.update(...)``,
    ``STRATEGIES.setdefault(...)``) outside ``analysis/strategies.py``.
    The registry's contract since the grammar closed is that every
    entry lowers from a ``ParallelSpec`` via ``register_spec_strategy``
    — that is what keeps the derived budgets/schedules auto-derivable
    and the ``tune plan`` candidate space equal to the strategy space."""
    if not ctx.strategy_scope:
        return
    if isinstance(node, ast.Call):
        callee = _dotted(node.func)
        tail = callee.rsplit(".", 1)[-1]
        if tail == "StrategyMeta":
            ctx.emit("TF120", node,
                     f"hand-built `{callee}(...)` outside "
                     f"analysis/strategies.py — register through "
                     f"strategies.register_spec_strategy(name, spec) so "
                     f"the budget/schedule derive from the grammar and "
                     f"the planner can enumerate it, or suppress with "
                     f"tf-lint: ok[TF120] and a reason", fn)
            return
        if (tail in ("update", "setdefault")
                and callee.rsplit(".", 2)[-2:-1] == ["STRATEGIES"]):
            ctx.emit("TF120", node,
                     f"`{callee}(...)` writes the strategy registry "
                     f"outside analysis/strategies.py — use "
                     f"strategies.register_spec_strategy(name, spec), "
                     f"or suppress with tf-lint: ok[TF120] and a "
                     f"reason", fn)
        return
    if isinstance(node, ast.Assign):
        for tgt in node.targets:
            if (isinstance(tgt, ast.Subscript)
                    and _dotted(tgt.value).rsplit(".", 1)[-1]
                    == "STRATEGIES"):
                ctx.emit("TF120", node,
                         "subscript write into STRATEGIES outside "
                         "analysis/strategies.py — use "
                         "strategies.register_spec_strategy(name, "
                         "spec), or suppress with tf-lint: ok[TF120] "
                         "and a reason", fn)
                return


@_node_rule
def _tf121_swap_seam(ctx: FileContext, node, fn):
    """Live weights mutated behind the swap seam's back: an assignment
    to any ``.params`` attribute — or a ``setattr(x, "params", ...)`` —
    inside the rollout-bearing modules.  The engine's ``swap_params()``
    is the one sanctioned setter because it validates the incoming tree
    structure and every leaf's shape/dtype against what the AOT table
    was compiled for; a raw rebind skips that and the compile-cache
    hit floor (and worse, numerical sanity) silently goes with it."""
    if not ctx.swap_scope:
        return
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for tgt in targets:
            if isinstance(tgt, ast.Attribute) and tgt.attr == "params":
                ctx.emit(
                    "TF121", node,
                    f"direct write to `{_dotted(tgt)}` bypasses the "
                    f"validating swap seam — go through "
                    f"engine.swap_params(new_params) (checks tree "
                    f"structure and leaf shapes/dtypes against the "
                    f"compiled AOT table), or suppress with tf-lint: "
                    f"ok[TF121] and a reason", fn)
                return
        return
    if isinstance(node, ast.Call):
        callee = _dotted(node.func)
        if (callee.rsplit(".", 1)[-1] == "setattr" and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value == "params"):
            ctx.emit(
                "TF121", node,
                "setattr(..., \"params\", ...) bypasses the validating "
                "swap seam — go through engine.swap_params(new_params), "
                "or suppress with tf-lint: ok[TF121] and a reason", fn)


@_node_rule
def _tf122_overlap_contract(ctx: FileContext, node, fn):
    """``declared_overlapped`` signed behind the strategy seam's back: a
    truthy (or dynamic) value for the keyword in a ``StrategyMeta(...)``
    or ``register_spec_strategy(...)`` call outside
    ``analysis/strategies.py``.  The declaration arms
    ``detect_exposed_comm`` as a hard gate, so the ONLY sanctioned call
    sites are the seam's own registrations — the ones whose compiled
    schedules the fixture pins actually watch.  Shares TF120's scope
    flag: the seam module itself is exempt."""
    if not ctx.strategy_scope or not isinstance(node, ast.Call):
        return
    callee = _dotted(node.func)
    tail = callee.rsplit(".", 1)[-1]
    if tail not in ("StrategyMeta", "register_spec_strategy"):
        return
    for kw in node.keywords:
        if kw.arg != "declared_overlapped":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and not v.value:
            return  # explicit False/None — not a signing
        ctx.emit("TF122", node,
                 f"`{callee}(..., declared_overlapped=...)` signs the "
                 f"overlap contract outside analysis/strategies.py — "
                 f"the declaration turns shardflow's exposed-comm "
                 f"detector into a hard gate, and only the strategy "
                 f"seam's registrations are covered by the pinned "
                 f"schedule fixtures; register through the seam, or "
                 f"suppress with tf-lint: ok[TF122] and a reason", fn)
        return


@_node_rule
def _tf123_span_seam(ctx: FileContext, node, fn):
    """Raw span emission behind the tracing seam's back: an
    ``events.emit("span_open"/"span_close"/"span_note", ...)`` call
    outside ``obs/tracing.py``.  Span records carry pairing invariants
    the schema cannot express — a hand-rolled emit skips span-id
    minting and the open-span registry, so the verifier counts its
    spans as leaked/orphaned and the ``tpuframe_open_spans`` gauge
    drifts.  Matches the same receiver shapes as TF112."""
    if (not ctx.trace_scope
            or not isinstance(node, ast.Call)
            or not isinstance(node.func, ast.Attribute)
            or node.func.attr != "emit"
            or _dotted(node.func.value).rsplit(".", 1)[-1]
            not in _EMIT_RECEIVERS
            or not node.args
            or not isinstance(node.args[0], ast.Constant)
            or node.args[0].value not in _SPAN_EVENT_LITERALS):
        return
    ctx.emit("TF123", node,
             f"events.emit({node.args[0].value!r}) outside "
             f"obs/tracing.py — raw span records bypass span-id "
             f"minting and the open-span registry (the verifier will "
             f"count them leaked/orphaned); use tracing.open_span/"
             f"close_span/span/note, or suppress with "
             f"tf-lint: ok[TF123] and a reason", fn)


@_fn_rule
def _tf103_timing(ctx: FileContext, fn: _FnInfo):
    node = fn.node
    timing_names: set[str] = set()
    has_device_work = False
    has_sync = False
    durations = []

    def is_timing_call(c):
        return (isinstance(c, ast.Call)
                and _dotted(c.func).rsplit(".", 1)[-1]
                in ("time", "perf_counter", "monotonic"))

    local = list(_iter_local(node))
    for child in local:
        if isinstance(child, ast.Assign) and is_timing_call(child.value):
            for t in child.targets:
                if isinstance(t, ast.Name):
                    timing_names.add(t.id)
        if isinstance(child, ast.Call):
            callee = _dotted(child.func)
            tail = callee.rsplit(".", 1)[-1]
            if tail in _SYNC_MARKERS:
                has_sync = True
            elif _DEVICE_WORK_RE.search(tail):
                has_device_work = True
    for child in local:
        if isinstance(child, ast.BinOp) and isinstance(
                child.op, ast.Sub):
            sides = (child.left, child.right)
            if all(is_timing_call(s)
                   or (isinstance(s, ast.Name)
                       and s.id in timing_names)
                   for s in sides) and (
                    timing_names or any(map(is_timing_call, sides))):
                durations.append(child)
    if durations and has_device_work and not has_sync:
        for d in durations:
            ctx.emit("TF103", d,
                     "duration measured around dispatched device work "
                     "with no block_until_ready/sync in scope — this "
                     "times dispatch, not execution", fn)


# ---------------------------------------------------------------------------
# TF114 — lock discipline (file rule: needs the class-level view).
# ---------------------------------------------------------------------------


def _is_lock_ctor(value) -> bool:
    return (isinstance(value, ast.Call)
            and _dotted(value.func).rsplit(".", 1)[-1] in _LOCK_CTOR_TAILS)


def _assign_target_attrs(node):
    """Flattened assignment-target list for Assign/AugAssign/Delete —
    tuple targets (``a, self.b = ...``) included."""
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, ast.AugAssign):
        targets = [node.target]
    elif isinstance(node, ast.Delete):
        targets = list(node.targets)
    else:
        return
    while targets:
        t = targets.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            targets.extend(t.elts)
        else:
            yield t


def _locked_by(with_node: ast.With, lock_exprs: set[str]) -> bool:
    return any(_dotted(item.context_expr) in lock_exprs
               for item in with_node.items)


def _tf114_walk(ctx, lock_exprs, mutated_cb, node, locked):
    """Walk one subtree tracking ``with <lock>:`` nesting.  Nested defs
    are descended with ``locked=False`` — their bodies run whenever the
    function is *called* (usually on the worker thread), not where it
    is defined, so a lock held at definition time proves nothing."""
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        for sub in node.body:
            _tf114_walk(ctx, lock_exprs, mutated_cb, sub, False)
        return
    if isinstance(node, ast.With):
        inner = locked or _locked_by(node, lock_exprs)
        for sub in node.body:
            _tf114_walk(ctx, lock_exprs, mutated_cb, sub, inner)
        return
    if not locked:
        mutated_cb(node)
    for child in ast.iter_child_nodes(node):
        _tf114_walk(ctx, lock_exprs, mutated_cb, child, locked)


@_file_rule
def _tf114_lock_discipline(ctx: FileContext):
    """Within _LOCK_DISCIPLINE_PARTS: a class owning a lock attribute
    (``self._lock = threading.Lock()``) must mutate its other instance
    attributes only under ``with self._lock:``; a module owning a
    module-level lock must mutate its ``global``-declared state only
    under that lock.  ~30 lines of logic on top of the shared
    scaffolding — the template for future rules."""
    if not ctx.lock_scope:
        return
    for cls in [n for n in ast.walk(ctx.tree)
                if isinstance(n, ast.ClassDef)]:
        locks = {t.attr for m in ast.walk(cls)
                 if isinstance(m, ast.Assign) and _is_lock_ctor(m.value)
                 for t in m.targets
                 if isinstance(t, ast.Attribute)
                 and isinstance(t.value, ast.Name) and t.value.id == "self"}
        if not locks:
            continue
        lock_exprs = {f"self.{name}" for name in locks}
        for meth in [m for m in cls.body
                     if isinstance(m, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                     and m.name not in _CTOR_METHODS]:
            info = _FnInfo(meth, traced=False)

            def mutated(stmt, meth=meth, info=info):
                for t in _assign_target_attrs(stmt):
                    base = t.value if isinstance(t, ast.Subscript) else t
                    if (isinstance(base, ast.Attribute)
                            and isinstance(base.value, ast.Name)
                            and base.value.id == "self"
                            and base.attr not in locks):
                        ctx.emit("TF114", stmt,
                                 f"self.{base.attr} mutated outside "
                                 f"`with self.{sorted(locks)[0]}:` in "
                                 f"{cls.name}.{meth.name}() — this class "
                                 f"declares its state shared by owning a "
                                 f"lock, and this module runs background "
                                 f"threads; hold the lock, or suppress "
                                 f"with tf-lint: ok[TF114] and a reason "
                                 f"if the site is provably "
                                 f"caller-serialized", info)
                if (isinstance(stmt, ast.Call)
                        and isinstance(stmt.func, ast.Attribute)
                        and stmt.func.attr in _MUTATING_METHODS
                        and isinstance(stmt.func.value, ast.Attribute)
                        and isinstance(stmt.func.value.value, ast.Name)
                        and stmt.func.value.value.id == "self"):
                    ctx.emit("TF114", stmt,
                             f"self.{stmt.func.value.attr}."
                             f"{stmt.func.attr}() mutates shared "
                             f"container state outside `with self."
                             f"{sorted(locks)[0]}:` in {cls.name}."
                             f"{meth.name}() — hold the lock, or "
                             f"suppress with tf-lint: ok[TF114] and a "
                             f"reason", info)

            _tf114_walk(ctx, lock_exprs, mutated, meth, False)
    # Module-level locks guard module globals the same way.
    mod_locks = {t.id for stmt in ctx.tree.body
                 if isinstance(stmt, ast.Assign)
                 and _is_lock_ctor(stmt.value)
                 for t in stmt.targets if isinstance(t, ast.Name)}
    if not mod_locks:
        return
    for func in _nested_defs(ctx.tree):
        declared = {n for s in ast.walk(func)
                    if isinstance(s, ast.Global) for n in s.names}
        if not declared:
            continue
        info = _FnInfo(func, traced=False)

        def g_mutated(stmt, func=func, info=info, declared=declared):
            for t in _assign_target_attrs(stmt):
                base = t.value if isinstance(t, ast.Subscript) else t
                if (isinstance(base, ast.Name) and base.id in declared
                        and base.id not in mod_locks):
                    ctx.emit("TF114", stmt,
                             f"global {base.id} mutated outside "
                             f"`with {sorted(mod_locks)[0]}:` in "
                             f"{func.name}() — this module guards its "
                             f"globals with a module-level lock; hold "
                             f"it, or suppress with tf-lint: ok[TF114] "
                             f"and a reason", info)

        _tf114_walk(ctx, mod_locks, g_mutated, func, False)


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------


def _visit_fn(ctx: FileContext, node, enclosing_traced: bool):
    traced = (enclosing_traced
              or node.name in ctx.jitted
              or any(_is_tracing_decorator(d)
                     for d in node.decorator_list))
    info = _FnInfo(node, traced, probes_backend=_probes_backend(node))
    for rule in _FN_RULES:
        rule(ctx, info)
    for child in _iter_local(node):
        for rule in _NODE_RULES:
            rule(ctx, child, info)
    for sub in _nested_defs(node):
        _visit_fn(ctx, sub, traced)


def lint_source(src: str, path: str = "<string>") -> list[LintFinding]:
    """Run every rule over one source blob; suppressions already applied."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [LintFinding("TF100", path, e.lineno or 0,
                            f"syntax error: {e.msg}")]
    ctx = FileContext(tree, src, path)
    for top in _iter_local(tree):
        for rule in _NODE_RULES:
            rule(ctx, top, None)   # module level: TF104 still applies
    for top in _nested_defs(tree):
        _visit_fn(ctx, top, False)
    for rule in _FILE_RULES:
        rule(ctx)
    return ctx.findings


def lint_paths(paths, exclude: tuple[str, ...] = ()) -> list[LintFinding]:
    """Lint every ``.py`` under each path (file or directory tree)."""
    findings: list[LintFinding] = []
    for p in paths:
        p = Path(p)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            rel = str(f)
            if any(part in rel for part in exclude):
                continue
            try:
                src = f.read_text()
            except OSError as e:
                findings.append(LintFinding("TF100", rel, 0, str(e)))
                continue
            findings.extend(lint_source(src, rel))
    return findings
