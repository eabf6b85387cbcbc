"""tpuframe.analysis — the offline static SPMD/collective lint.

Each layer is tested against a *seeded defect* plus its clean twin:

  Layer 1 (HLO):   a mis-sharded matmul whose contraction dim is sharded
                   materializes a full all-gather that the dp budget never
                   declared; the correctly sharded twin emits nothing.
  Layer 2 (jaxpr): a bf16 step with one hidden ``.astype(float32)`` off
                   the MXU path; a captured host constant; a donation
                   alias table diffed against its declaration.
  Layer 3 (AST):   one snippet per rule (TF101-TF104) that must fire,
                   a clean twin that must not, and the suppression
                   contract — plus the shipped ``tpuframe/`` tree, which
                   must self-lint clean (the CI gate's fast half).

Also here: the per-strategy budget audits over the REAL step programs
(skipping strategies this jax cannot express), the KNOWN_VMEM_EXCLUSIONS
registry cross-check, and the legacy-shard_map dp numerical parity run
referenced by tpuframe/parallel/step.py (check_rep=False disables the
psum-transpose rewrite; the explicit grad reduction must keep the dp
step bit-comparable to the single-device step).
"""

import functools
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuframe.analysis import (budgets, hlo_audit, jaxpr_checks,
                               source_lint, strategies)
from tpuframe.obs import spmd_check
from tpuframe.parallel import mesh as mesh_lib, step as step_lib


# ---------------------------------------------------------------------------
# Layer 1 mechanism: parsing HLO / StableHLO text.
# ---------------------------------------------------------------------------


def test_parse_collectives_kinds_and_bytes():
    txt = """
      %ar = f32[4,25]{1,0} all-reduce(%x), replica_groups={{0,1}}
      %ag = bf16[1024,1024]{1,0} all-gather(%y), dimensions={0}
      %cp = f32[128,128]{1,0} collective-permute(%z)
      %aa = f32[64,64]{1,0} all-to-all(%w)
    """
    rep = hlo_audit.parse_collectives(txt)
    by = rep.bytes_by_kind()
    assert by["all-reduce"] == 400
    assert by["all-gather"] == 1024 * 1024 * 2
    assert by["collective-permute"] == 128 * 128 * 4
    assert by["all-to-all"] == 64 * 64 * 4
    ar = [op for op in rep.ops if op.kind == "all-reduce"][0]
    assert ar.replica_groups == "{0,1}"


def test_parse_collectives_async_forms():
    # -start tuples alias the operand (halved); all-gather-start keeps the
    # gathered output; -done must not double count.
    txt = """
      %s = (f32[100]{0}, f32[100]{0}) all-reduce-start(%x)
      %d = f32[100]{0} all-reduce-done(%s)
      %g = (f32[8,16]{1,0}, f32[64,16]{1,0}) all-gather-start(%y)
      %gd = f32[64,16]{1,0} all-gather-done(%g)
    """
    rep = hlo_audit.parse_collectives(txt)
    assert rep.count_by_kind() == {"all-reduce": 1, "all-gather": 1}
    assert rep.bytes_by_kind()["all-reduce"] == 400
    assert rep.bytes_by_kind()["all-gather"] == 64 * 16 * 4


def test_parse_collectives_reduce_scatter_counts_operand():
    # The full operand crosses the wire even though the result is the
    # scattered shard.
    txt = "%rs = f32[16,128]{1,0} reduce-scatter(f32[128,128]{1,0} %x)"
    rep = hlo_audit.parse_collectives(txt)
    assert rep.bytes_by_kind()["reduce-scatter"] == 128 * 128 * 4


def test_parse_collectives_stablehlo_form():
    txt = ('%0 = "stablehlo.all_reduce"(%arg0) ({...}) '
           '{replica_groups = dense<[[0,1,2,3]]>} '
           ': (tensor<128x256xf32>) -> tensor<128x256xf32>')
    rep = hlo_audit.parse_collectives(txt)
    assert rep.bytes_by_kind() == {"all-reduce": 128 * 256 * 4}


def test_legacy_allreduce_payload_surface():
    # perf/_hlo_parse.py promotion: the legacy shape of the API survives.
    payload, ops = hlo_audit.allreduce_payload(
        "%r = (bf16[100]{0}, f32[10]{0}) all-reduce(%a, %b)")
    assert payload == {"bf16": 200, "f32": 40} and ops == 1


# ---------------------------------------------------------------------------
# Layer 1 policy: budgets.
# ---------------------------------------------------------------------------


def _report(txt):
    return hlo_audit.parse_collectives(textwrap.dedent(txt))


def test_budget_flags_undeclared_kind():
    rep = _report("%cp = f32[1024,1024]{1,0} collective-permute(%x)")
    v = budgets.check_budget(rep, budgets.dp_budget(1 << 20))
    assert len(v) == 1 and "undeclared collective kind" in v[0]
    assert "collective-permute" in v[0]


def test_budget_flags_cap_exceeded():
    rep = _report("%ar = f32[4096,4096]{1,0} all-reduce(%x)")  # 64 MB
    v = budgets.check_budget(rep, budgets.dp_budget(1 << 20))  # cap 2 MB
    assert len(v) == 1 and "budget exceeded" in v[0]


def test_budget_ignore_floor_and_clean_pass():
    rep = _report("""
      %m = f32[1]{0} all-reduce(%metric)
      %cp = f32[16]{0} collective-permute(%tiny)
      %g = f32[131072]{0} all-reduce(%grads)
    """)
    # Sub-floor metric scalars and stray tiny ops never violate; the
    # param-sized all-reduce fits its declaration.
    assert budgets.check_budget(rep, budgets.dp_budget(512 * 1024)) == []


def test_budget_total_cap():
    rep = _report("%ar = f32[1048576]{0} all-reduce(%x)")  # 4 MB
    b = budgets.CommBudget(name="t", allowed={"all-reduce": None},
                           max_total_bytes=1 << 20)
    v = budgets.check_budget(rep, b)
    assert len(v) == 1 and "total collective bytes" in v[0]


def test_budget_rejects_unknown_kind_declaration():
    with pytest.raises(ValueError, match="unknown collective kind"):
        budgets.CommBudget(name="t", allowed={"all-scatter": 1})


def test_strategy_budget_dispatch():
    b = budgets.strategy_budget("dp", param_bytes=100)
    assert b.allowed["all-reduce"] == 200
    with pytest.raises(ValueError, match="no declared budget"):
        budgets.strategy_budget("zmq-parallel")


# ---------------------------------------------------------------------------
# Layer 1 end to end: the seeded mis-sharding.
# ---------------------------------------------------------------------------


def _matmul_program(mesh, w_spec):
    xs = NamedSharding(mesh, P("data", None))
    ws = NamedSharding(mesh, w_spec)
    out = NamedSharding(mesh, P("data", None))
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=xs)
    w = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=ws)
    return jax.jit(lambda x, w: x @ w, out_shardings=out), (x, w)


def test_missharded_matmul_breaks_dp_budget(mesh8):
    # w sharded along the CONTRACTING dim while x's columns are
    # replicated: GSPMD must materialize the full 4 MB weight all-gather
    # — the exact class of silent mistake the gate exists to catch.
    jitted, args = _matmul_program(mesh8, P("data", None))
    report, _ = hlo_audit.audit_jitted(jitted, *args)
    assert report.bytes_by_kind(1 << 20).get("all-gather", 0) \
        == 1024 * 1024 * 4
    v = budgets.check_budget(report, budgets.dp_budget(64 * 1024))
    assert v and "all-gather" in v[0]


def test_well_sharded_matmul_passes_dp_budget(mesh8):
    jitted, args = _matmul_program(mesh8, P())
    report, _ = hlo_audit.audit_jitted(jitted, *args)
    assert budgets.check_budget(report, budgets.dp_budget(64 * 1024)) == []


# ---------------------------------------------------------------------------
# Layer 2: jaxpr checks.
# ---------------------------------------------------------------------------


def test_find_f32_matmuls_catches_hidden_upcast():
    def bad_step(x, w1, w2):
        h = jnp.tanh(x @ w1)
        # The seeded defect: one matmul quietly runs in f32.
        return (h.astype(jnp.float32) @ w2.astype(jnp.float32)).sum()

    x = jnp.zeros((8, 16), jnp.bfloat16)
    w = jnp.zeros((16, 16), jnp.bfloat16)
    traced = jax.make_jaxpr(bad_step)(x, w, w)
    assert jaxpr_checks.has_bf16(traced)
    findings = jaxpr_checks.find_f32_matmuls(traced)
    assert len(findings) == 1
    assert findings[0].primitive == "dot_general"
    assert "float32" in findings[0].dtypes


def test_find_f32_matmuls_clean_bf16_step():
    def good_step(x, w1, w2):
        # f32 accumulation of the LOSS is legitimate — only MXU ops count.
        return (jnp.tanh(x @ w1) @ w2).astype(jnp.float32).sum()

    x = jnp.zeros((8, 16), jnp.bfloat16)
    w = jnp.zeros((16, 16), jnp.bfloat16)
    traced = jax.make_jaxpr(good_step)(x, w, w)
    assert jaxpr_checks.has_bf16(traced)
    assert jaxpr_checks.find_f32_matmuls(traced) == []


def test_find_large_constants():
    baked = np.ones((600, 600), np.float32)  # 1.44 MB closed over

    def leaky(x):
        return (x * jnp.asarray(baked)).sum()

    traced = jax.make_jaxpr(leaky)(jnp.zeros((600, 600), jnp.float32))
    findings = jaxpr_checks.find_large_constants(traced)
    assert findings and findings[0].nbytes == 600 * 600 * 4
    # Below-threshold constants are not hoarded.
    assert jaxpr_checks.find_large_constants(traced, min_bytes=2 << 20) == []


def test_parse_input_output_alias():
    hlo = ("HloModule jit_step, input_output_alias={ {0}: (0, {}, "
           "may-alias), {1}: (2, {1}, must-alias) }, "
           "entry_computation_layout={...}")
    assert jaxpr_checks.parse_input_output_alias(hlo) == {0, 2}
    assert jaxpr_checks.parse_input_output_alias("HloModule bare") == set()


def test_donation_report_leak_accounting():
    rep = jaxpr_checks.audit_donation(
        "HloModule m, input_output_alias={ {0}: (1, {}, may-alias) }",
        declared={1, 3}, platform="tpu")
    assert rep.aliased == {1}
    assert rep.leaked == {3}
    assert rep.platform_supports
    assert "leaked=1" in str(rep)


def test_donation_audit_cpu_backend_honesty(mesh8):
    # XLA:CPU ignores donation — the audit must say "can't tell here"
    # instead of reporting a mass leak (the TPU AOT path gives the real
    # answer; see tests/test_aot_tpu_compile.py).
    jitted = jax.jit(lambda s: jax.tree.map(lambda a: a + 1, s),
                     donate_argnums=(0,))
    compiled = jitted.lower({"w": jnp.zeros((128, 128))}).compile()
    rep = jaxpr_checks.audit_donation(compiled, declared={0},
                                      platform="cpu")
    assert rep.platform_supports or not rep.aliased


# ---------------------------------------------------------------------------
# Layer 3: source lint.
# ---------------------------------------------------------------------------


def _rules(src):
    return [f.rule for f in source_lint.lint_source(textwrap.dedent(src))]


def test_tf101_host_conversion_in_jitted_code():
    assert _rules("""
        import jax, numpy as np

        @jax.jit
        def f(x):
            y = float(x)
            z = np.asarray(x)
            return x
    """) == ["TF101", "TF101"]


def test_tf101_item_method_and_jit_by_name():
    # g is traced because it is PASSED to jax.jit, not decorated.
    assert _rules("""
        import jax

        def g(x):
            return x.item()

        step = jax.jit(g)
    """) == ["TF101"]


def test_tf101_host_code_is_allowed_to_convert():
    assert _rules("""
        def report(metrics):
            return float(metrics["loss"])
    """) == []


def test_tf102_python_branch_on_array():
    assert _rules("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
    """) == ["TF102"]


def test_tf102_static_config_branch_is_fine():
    assert _rules("""
        import jax

        @jax.jit
        def f(x, axes=()):
            if axes:
                return x
            return -x
    """) == []


def test_tf103_timing_without_sync():
    assert _rules("""
        import time

        def bench(step, batch):
            t0 = time.perf_counter()
            step(batch)
            t1 = time.perf_counter()
            return t1 - t0
    """) == ["TF103"]


def test_tf103_sync_in_scope_is_clean():
    assert _rules("""
        import time
        import jax

        def bench(step, batch):
            t0 = time.perf_counter()
            jax.block_until_ready(step(batch))
            t1 = time.perf_counter()
            return t1 - t0
    """) == []


def test_tf104_pallas_call_must_decide_interpret():
    assert _rules("""
        from jax.experimental import pallas as pl

        def kernel_call(x):
            return pl.pallas_call(my_kernel, out_shape=x)(x)
    """) == ["TF104"]
    assert _rules("""
        from jax.experimental import pallas as pl

        def kernel_call(x):
            return pl.pallas_call(my_kernel, out_shape=x,
                                  interpret=_auto_interpret())(x)
    """) == []


def test_lint_suppression_contract():
    # Targeted suppression silences exactly its rule...
    assert _rules("""
        import jax

        @jax.jit
        def f(x):
            return float(x)  # tf-lint: ok[TF101]
    """) == []
    # ...a mismatched tag does not...
    assert _rules("""
        import jax

        @jax.jit
        def f(x):
            return float(x)  # tf-lint: ok[TF104]
    """) == ["TF101"]
    # ...and a def-line suppression covers the whole function.
    assert _rules("""
        import jax

        @jax.jit
        def f(x):  # tf-lint: ok
            return float(x)
    """) == []


def test_lint_nested_def_inherits_tracedness():
    assert _rules("""
        import jax

        @jax.jit
        def outer(x):
            def inner(y):
                return float(y)
            return inner(x)
    """) == ["TF101"]


def test_tf105_raw_gcs_call_outside_gcs_layer():
    src = """
        def fetch(bucket, key):
            return bucket.blob(key).download_as_bytes()
    """
    findings = source_lint.lint_source(textwrap.dedent(src),
                                       "tpuframe/data/loader.py")
    assert [f.rule for f in findings] == ["TF105"]
    # ...and uploads / listings too
    src2 = """
        def push(bucket, key, data):
            bucket.blob(key).upload_from_string(data)
            return list(client.list_blobs(bucket))
    """
    findings2 = source_lint.lint_source(textwrap.dedent(src2),
                                        "tpuframe/ckpt/uploader.py")
    assert [f.rule for f in findings2] == ["TF105", "TF105"]


def test_tf105_gcs_layer_itself_is_exempt():
    src = """
        def _read_bytes_once(path):
            return _client().bucket(b).blob(k).download_as_bytes(timeout=60)
    """
    assert source_lint.lint_source(textwrap.dedent(src),
                                   "tpuframe/data/gcs.py") == []


def test_tf105_unbounded_sleep_retry_loop():
    assert _rules("""
        import time

        def poll(path):
            while True:
                if fetch(path):
                    break
                time.sleep(1.0)
    """) == ["TF105"]


def test_tf105_bounded_retry_loops_are_clean():
    # a comparison (attempt bound) in the loop body makes it bounded...
    assert _rules("""
        import time

        def poll(path):
            attempt = 0
            while True:
                attempt += 1
                if attempt >= 5:
                    return None
                time.sleep(1.0)
    """) == []
    # ...as does reading a clock (deadline pattern), or raising
    assert _rules("""
        import time

        def poll(deadline):
            while True:
                now = time.monotonic()
                time.sleep(1.0)
    """) == []
    # and a non-`while True` loop never matches at all
    assert _rules("""
        import time

        def poll(tries):
            while tries:
                tries -= 1
                time.sleep(1.0)
    """) == []


def test_tf105_suppression():
    assert _rules("""
        import time

        def forever():
            while True:  # tf-lint: ok[TF105]
                time.sleep(60.0)
    """) == []


def test_tf107_print_and_clock_in_hot_path():
    src = textwrap.dedent("""
        import time

        def make_batch(it):
            t0 = time.time()
            batch = next(it)
            print("batch in", time.time() - t0)
            return batch
    """)
    findings = source_lint.lint_source(src, "tpuframe/data/pipeline.py")
    assert [f.rule for f in findings] == ["TF107", "TF107", "TF107"]
    # The identical code outside a hot-path module is host code doing
    # host things — no finding.
    assert source_lint.lint_source(src, "tpuframe/launch/launcher.py") == []


def test_tf107_print_in_traced_code_fires_anywhere():
    assert _rules("""
        import jax

        @jax.jit
        def step(x):
            print("loss", x)
            return x * 2
    """) == ["TF107"]


def test_tf107_obs_routed_instrumentation_is_clean():
    src = textwrap.dedent("""
        from tpuframe.obs import events, metrics

        def make_batch(it):
            batch = next(it)
            metrics.bump("data.batches")
            events.emit("step", step=0, wall_ms=1.0)
            return batch
    """)
    assert source_lint.lint_source(src, "tpuframe/data/pipeline.py") == []
    # Module-level clock reads (import-time, not per-step) don't fire.
    mod = "import time\n_T0 = time.time()\n"
    assert source_lint.lint_source(mod, "tpuframe/parallel/step.py") == []


def test_tf107_suppression():
    src = textwrap.dedent("""
        def debug_batch(b):
            print("shape", b)  # tf-lint: ok[TF107]
    """)
    assert source_lint.lint_source(src, "tpuframe/data/pipeline.py") == []


def test_tf111_thread_outside_sanctioned_modules():
    # A stray thread calling into collectives deadlocks a pod, so thread
    # creation is reviewable policy: only the background-work homes may
    # construct one (docs/DESIGN.md "Async checkpointing").
    src = textwrap.dedent("""
        import threading

        def uploader(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
    """)
    findings = source_lint.lint_source(src, "tpuframe/train.py")
    assert [f.rule for f in findings] == ["TF111"]
    for sanctioned in ("tpuframe/ckpt/checkpoint.py",
                       "tpuframe/data/pipeline.py",
                       "tpuframe/obs/heartbeat.py",
                       "tpuframe/launch/launcher.py"):
        assert source_lint.lint_source(src, sanctioned) == [], sanctioned


def test_tf111_sanctions_the_timeline_watcher_and_only_there():
    # The device watcher of obs/timeline.py waits on results its caller
    # launched and never launches: sanctioned by path, with no suppression
    # in the file; the same source anywhere else is a finding.
    import tpuframe.obs.timeline as timeline_mod

    src = open(timeline_mod.__file__).read()
    assert "threading.Thread(" in src and "ok[TF111]" not in src
    assert [f.rule for f in source_lint.lint_source(
        src, "tpuframe/obs/timeline.py") if f.rule == "TF111"] == []
    assert [f.rule for f in source_lint.lint_source(
        src, "tpuframe/obs/metrics.py") if f.rule == "TF111"] == ["TF111"]


def test_tf111_bare_thread_import_and_module_level():
    src = textwrap.dedent("""
        from threading import Thread

        worker = Thread(target=print)
    """)
    findings = source_lint.lint_source(src, "tpuframe/parallel/step.py")
    assert [f.rule for f in findings] == ["TF111"]


def test_tf111_suppression():
    src = textwrap.dedent("""
        import threading

        def sampler():
            t = threading.Thread(target=print)  # tf-lint: ok[TF111]
            t.start()
    """)
    assert source_lint.lint_source(src, "tpuframe/obs/devmem.py") == []


def test_tf114_unlocked_mutation_in_lock_owning_class():
    # A class that owns a lock has declared its state shared; mutating
    # another attribute without holding the lock is the statically
    # visible race (the contract the ckpt/obs worker threads rely on).
    src = textwrap.dedent("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def push(self, x):
                self.items.append(x)

            def reset(self):
                self.items = []
    """)
    findings = source_lint.lint_source(src, "tpuframe/ckpt/worker.py")
    assert [f.rule for f in findings] == ["TF114", "TF114"]
    assert "push" in findings[0].message
    assert "reset" in findings[1].message
    # same source outside the background-thread modules: out of scope
    assert source_lint.lint_source(src, "tpuframe/train.py") == []


def test_tf114_locked_and_ctor_mutations_are_clean():
    src = textwrap.dedent("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def push(self, x):
                with self._lock:
                    self.items.append(x)
                    self.count = len(self.items)
    """)
    assert source_lint.lint_source(src, "tpuframe/ckpt/worker.py") == []
    # a class with no lock never opted in — nothing to check against
    lockless = textwrap.dedent("""
        class Plain:
            def bump(self):
                self.n = 1
    """)
    assert source_lint.lint_source(lockless,
                                   "tpuframe/ckpt/worker.py") == []


def test_tf114_worker_closure_runs_unlocked():
    # A nested def's body executes when the WORKER calls it, not where
    # it is defined — a lock held at definition time proves nothing.
    src = textwrap.dedent("""
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.Lock()
                self.errors = []

            def start(self):
                with self._lock:
                    def work():
                        self.errors.append("boom")
                    return work
    """)
    findings = source_lint.lint_source(src, "tpuframe/ckpt/manager.py")
    assert [f.rule for f in findings] == ["TF114"]
    assert "errors" in findings[0].message


def test_tf114_module_level_lock_guards_globals():
    src = textwrap.dedent("""
        import threading

        _lock = threading.Lock()
        _active = None

        def stop():
            global _active
            _active = None

        def start(x):
            global _active
            with _lock:
                _active = x
    """)
    findings = source_lint.lint_source(src, "tpuframe/obs/exporter.py")
    assert [f.rule for f in findings] == ["TF114"]
    assert "stop" in findings[0].message and "_active" in findings[0].message


def test_tf114_suppression():
    src = textwrap.dedent("""
        import threading

        class Recorder:
            def __init__(self):
                self._lock = threading.Lock()
                self.path = None

            def dump(self, p):
                self.path = p  # tf-lint: ok[TF114]
    """)
    assert source_lint.lint_source(src, "tpuframe/obs/flight.py") == []


def test_tf117_sync_barrier_in_traced_hot_path():
    # A block_until_ready inside a traced function in parallel/ serializes
    # the very overlap the schedule auditor scores — fires on both the
    # module-level and method spellings.
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            y = x * 2
            jax.block_until_ready(y)
            return y.block_until_ready()
    """)
    findings = source_lint.lint_source(src, "tpuframe/parallel/step.py")
    assert [f.rule for f in findings] == ["TF117", "TF117"]
    # serve/engine.py is the other declared hot path.
    findings = source_lint.lint_source(src, "tpuframe/serve/engine.py")
    assert [f.rule for f in findings] == ["TF117", "TF117"]


def test_tf117_device_get_in_traced_hot_path():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def decode(tok):
            return jax.device_get(tok)
    """)
    findings = source_lint.lint_source(src, "tpuframe/serve/engine.py")
    assert [f.rule for f in findings] == ["TF117"]


def test_tf117_untraced_and_out_of_scope_are_clean():
    # The same barriers in an UNtraced driver loop are the legitimate
    # spelling (that's where obs timing is supposed to sync)...
    untraced = textwrap.dedent("""
        import jax

        def drive(step, x):
            out = step(x)
            jax.block_until_ready(out)
            return jax.device_get(out)
    """)
    assert source_lint.lint_source(
        untraced, "tpuframe/parallel/step.py") == []
    # ...and traced code outside the declared hot paths is not this
    # rule's business (TF101/TF107 own the general cases).
    traced = textwrap.dedent("""
        import jax

        @jax.jit
        def bench(x):
            jax.block_until_ready(x)
            return x
    """)
    assert source_lint.lint_source(traced, "tpuframe/obs/bench.py") == []


def test_tf117_suppression():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            jax.block_until_ready(x)  # tf-lint: ok[TF117]
            return x
    """)
    assert source_lint.lint_source(src, "tpuframe/parallel/step.py") == []


def test_tf118_raw_network_call_outside_fleet_seams():
    # Fleet traffic without a RetryPolicy is the raw-GCS bypass class at
    # the serving boundary: no backoff, no deadline, no obs counters.
    src = textwrap.dedent("""
        import socket
        import urllib.request

        def probe(url):
            with urllib.request.urlopen(url, timeout=1.0) as r:
                return r.read()

        def dial(host):
            return socket.create_connection((host, 80))
    """)
    findings = source_lint.lint_source(src, "tpuframe/serve/scheduler.py")
    assert [f.rule for f in findings] == ["TF118", "TF118"]
    # The sanctioned seams: the router's transport and the exporter.
    assert source_lint.lint_source(src, "tpuframe/serve/router.py") == []
    assert source_lint.lint_source(src, "tpuframe/obs/exporter.py") == []


def test_tf118_bare_and_http_client_shapes():
    src = textwrap.dedent("""
        from urllib.request import urlopen
        import http.client

        def fetch(url):
            return urlopen(url).read()

        def connect(host):
            return http.client.HTTPConnection(host)
    """)
    findings = source_lint.lint_source(src, "tpuframe/resilience/policy.py")
    assert [f.rule for f in findings] == ["TF118", "TF118"]


def test_tf118_non_client_socket_use_is_clean():
    # gethostname/socketpair are not fleet traffic — no finding.
    src = textwrap.dedent("""
        import socket

        def host():
            return socket.gethostname()
    """)
    assert source_lint.lint_source(src, "tpuframe/obs/events.py") == []


def test_tf118_suppression():
    src = textwrap.dedent("""
        import socket

        def free_port():
            with socket.socket() as s:  # tf-lint: ok[TF118]
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]
    """)
    assert source_lint.lint_source(src, "tpuframe/launch/launcher.py") == []


def test_shipped_tree_self_lints_clean():
    import tpuframe

    pkg = pathlib.Path(tpuframe.__file__).parent
    findings = source_lint.lint_paths([pkg])
    assert findings == [], "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# Strategy audits over the real step programs + registration surface.
# ---------------------------------------------------------------------------


# One compile per strategy: the budget test and the golden-loss test
# below share the audit (and with it the AOT executable).
_audit = functools.cache(strategies.audit_strategy)


@pytest.mark.parametrize("name", sorted(strategies.STRATEGIES))
def test_strategy_step_program_fits_declared_budget(name):
    audit = _audit(name)
    if audit.status == "unavailable":
        pytest.skip(audit.reason)
    assert audit.status == "ok", str(audit)
    assert audit.report is not None and audit.budget is not None


# Golden loss per surviving training strategy: the audited executable
# itself, run for real on the 8-device CPU mesh, must reproduce the
# single-device losses of the same model, init and batch.
_GOLDEN_STEPS = 3


@functools.cache
def _golden_reference(kind, batch_shape, rows_equal):
    """(params, tx, batch, single-device losses) for one toy program."""
    from tpuframe.models import losses
    from tpuframe.models.transformer_lm import LMConfig, ScanBlockLM

    if kind.startswith("pp"):
        # _pp_build's model; the caller's shape assert pins the copy.
        model = ScanBlockLM(LMConfig.tiny(
            vocab_size=64, hidden_size=32, num_layers=int(kind[2:]),
            num_heads=2, intermediate_size=64, max_seq=16))
        tx = optax.adamw(1e-3)

        def loss_fn(params, model_state, b, rng):
            logits = model.apply({"params": params}, b["input_ids"])
            return (losses.softmax_cross_entropy(logits, b["labels"]),
                    ({}, {}))
    else:
        pieces = (strategies._moe_pieces if kind == "moe"
                  else strategies._lm_pieces)
        model, loss_fn, tx, *_ = pieces()
    b, s = batch_shape
    # adasum(g, g) == g: its single-device twin exists only when every
    # replica holds the same row, so that is the batch it is run on.
    ids = np.random.default_rng(0).integers(
        0, 64, size=(1 if rows_equal else b, s + 1))
    ids = np.broadcast_to(ids, (b, s + 1)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}
    params = model.init(jax.random.key(0), batch["input_ids"][:1])["params"]
    step = step_lib.make_train_step(loss_fn, tx, None, donate=False)
    state = step_lib.TrainState.create(params, tx)
    want = []
    for _ in range(_GOLDEN_STEPS):
        state, m = step(state, batch)
        want.append(float(m["loss"]))
    return params, tx, batch, want


@pytest.mark.parametrize("name", sorted(
    n for n in strategies.STRATEGIES if n != "serve-dp-decode"))
def test_strategy_golden_loss_matches_single_device(name):
    import dataclasses

    from tpuframe.parallel import pspec

    audit = _audit(name)
    if audit.status == "unavailable":
        pytest.skip(audit.reason)
    builder = strategies.STRATEGIES[name]
    _, (state_sds, batch_sds), *_ = builder(8)
    spec = pspec.parse_spec(builder.args[0])
    kind = (f"pp{spec.pp}" if spec.pp > 1
            else "moe" if spec.ep > 1 else "lm")
    params, tx, batch, want = _golden_reference(
        kind, batch_sds["input_ids"].shape,
        builder.keywords.get("grad_reduce") == "adasum")
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape, state_sds.params))
    # adamw starts from zeros, so the strategy's own optimizer-state
    # layout (replicated, or zero1's flat padded vectors) fills as zeros.
    state = dataclasses.replace(
        step_lib.TrainState.create(jax.tree.map(jnp.copy, params), tx),
        opt_state=jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                               state_sds.opt_state))
    got = []
    for _ in range(_GOLDEN_STEPS):
        state, m = audit.compiled(state, batch)
        got.append(float(m["loss"]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert want[-1] < want[0], "training should make progress"


def test_dp_audit_sees_the_gradient_allreduce():
    # Wire-level guard for the legacy-shard_map grad fix (parallel/step.py
    # check_rep note): the dp program must carry a param-sized gradient
    # all-reduce — silently-local gradients would show (almost) none.
    audit = strategies.audit_strategy("dp")
    if audit.status == "unavailable":
        pytest.skip(audit.reason)
    # Per-leaf reductions may each sit under the budget floor — the TOTAL
    # gradient traffic is the invariant, so no min_bytes filter here.
    ar = audit.report.bytes_by_kind().get("all-reduce", 0)
    assert ar >= audit.param_bytes, audit.report.summary()


def test_check_step_program_budget_registration(mesh8):
    # The startup hash check and the budget audit run off one lowering.
    good, good_args = _matmul_program(mesh8, P())
    spmd_check.check_step_program(good, "good-matmul", *good_args,
                                  budget=budgets.dp_budget(64 * 1024))
    bad, bad_args = _matmul_program(mesh8, P("data", None))
    with pytest.raises(RuntimeError, match="budget violation"):
        spmd_check.audit_step_program(bad, "bad-matmul", *bad_args,
                                      budget=budgets.dp_budget(64 * 1024))


def test_known_exclusion_registry_matches_gate():
    from tpuframe.ops import fused_conv_bn

    assert budgets.check_known_exclusions() == []
    # The registered shape really is excluded by the VMEM gate...
    s = budgets.KNOWN_VMEM_EXCLUSIONS[0]["shape"]
    assert not fused_conv_bn.supported(s["h"], s["w"], s["n"], s["k"],
                                       s["c"])
    # ...while the neighbouring ResNet-50 1x1 shapes still fit.
    assert fused_conv_bn.supported(h=14, w=14, n=256, k=1024, c=512)


# ---------------------------------------------------------------------------
# Numerical parity: the legacy-shard_map dp step vs the single-device
# step (the verification promised in tpuframe/parallel/step.py).
# ---------------------------------------------------------------------------


def test_dp_step_matches_single_device_step(mesh8):
    def loss_fn(params, model_state, b, rng):
        pred = jnp.tanh(b["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2), ({}, {})

    k1, k2, k3, k4 = jax.random.split(jax.random.key(7), 4)
    params = {"w1": 0.1 * jax.random.normal(k1, (16, 32)),
              "w2": 0.1 * jax.random.normal(k2, (32, 4))}
    batch = {"x": jax.random.normal(k3, (32, 16)),
             "y": jax.random.normal(k4, (32, 4))}
    tx = optax.adam(1e-2)

    dp_step = step_lib.make_train_step(loss_fn, tx, mesh8, donate=False)
    ref_step = step_lib.make_train_step(loss_fn, tx, mesh=None,
                                        donate=False)
    dp_state = step_lib.TrainState.create(params, tx)
    ref_state = step_lib.TrainState.create(params, tx)
    for _ in range(3):
        dp_state, dp_metrics = dp_step(dp_state, batch)
        ref_state, ref_metrics = ref_step(ref_state, batch)

    np.testing.assert_allclose(dp_metrics["loss"], ref_metrics["loss"],
                               rtol=1e-5, atol=1e-7)
    for key in params:
        np.testing.assert_allclose(
            np.asarray(dp_state.params[key]),
            np.asarray(ref_state.params[key]),
            rtol=1e-5, atol=1e-6, err_msg=key)
