"""tpuframe.parallel.pspec — declarative parallelism specs lowered onto
hierarchical ICI×DCN meshes (ISSUE PR 15).

Golden invariants pinned here:

* the spec grammar round-trips (parse -> canonical -> parse) and rejects
  malformed or overcommitted strings with messages naming the defect —
  never a silent fallback;
* the hierarchical mesh puts the DCN ``slice`` axis OUTERMOST, and the
  slice-aware batch helpers (``batch_axes``/``data_parallel_size``/
  ``batch_spec``) range over it;
* spec lowering is a *naming* decision, never a numeric one: the
  spec-lowered dp / dp-zero1 / fsdp steps reproduce the hand-wired
  trajectories step for step (same rtol pin as test_zero1's golden);
* the composed ``dp=2,fsdp=2;slices=2`` strategy audits clean through
  all four shardflow detectors, its auto-derived budget matches the
  checked-in ``derived_budgets.json`` pin byte for byte, and the
  ICI/DCN comm split attributes nonzero bytes to the cross-slice axis;
* TF119 keeps raw ``jax.sharding.Mesh``/``jax.make_mesh`` construction
  out of everything but the mesh seam (parallel/mesh.py, pspec.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpuframe.analysis import collective_graph as cg
from tpuframe.analysis import shardflow, source_lint, strategies
from tpuframe.models import losses
from tpuframe.parallel import mesh as mesh_lib
from tpuframe.parallel import pspec
from tpuframe.parallel import step as step_lib
from tpuframe.parallel import zero1
from tpuframe.tune import roofline

COMPOSED = "dp=2,fsdp=2;slices=2"
COMPOSED_NAME = f"spec:{COMPOSED}"


# ----------------------------------------------------------------------
# grammar: round-trip, malformed, overcommitted
# ----------------------------------------------------------------------

class TestGrammar:
    @pytest.mark.parametrize("text,want", pspec._ROUNDTRIP_CASES)
    def test_round_trip(self, text, want):
        spec = pspec.parse_spec(text)
        assert spec.canonical() == want
        assert pspec.parse_spec(spec.canonical()) == spec

    def test_whitespace_is_insignificant(self):
        assert (pspec.parse_spec(" dp=4, fsdp=2 ; slices=2 ")
                == pspec.parse_spec("dp=4,fsdp=2;slices=2"))

    @pytest.mark.parametrize("text", pspec._MALFORMED_CASES)
    def test_malformed_rejected(self, text):
        with pytest.raises(pspec.SpecError):
            pspec.parse_spec(text)

    @pytest.mark.parametrize("text,n", pspec._OVERCOMMITTED_CASES)
    def test_overcommitted_rejected(self, text, n):
        with pytest.raises(pspec.SpecError,
                           match="overcommit|divide|does not fit"):
            pspec.parse_spec(text).sizes(n)

    def test_wildcard_dp_absorbs_remainder(self):
        sizes = pspec.parse_spec("dp=*,fsdp=2").sizes(8)
        assert sizes["data"] == 4 and sizes["fsdp"] == 2

    def test_composed_sizes_include_slice(self):
        sizes = pspec.parse_spec(COMPOSED).sizes(8)
        assert sizes[mesh_lib.SLICE_AXIS] == 2
        assert sizes["data"] == 2 and sizes["fsdp"] == 2

    def test_self_check_clean(self):
        assert pspec.check() == []


class TestResolve:
    @pytest.fixture(autouse=True)
    def clean_env(self, monkeypatch):
        monkeypatch.delenv(pspec.SPEC_ENV, raising=False)

    def test_default_is_none(self):
        assert pspec.resolve() == (None, "default")

    def test_env_wins(self, monkeypatch):
        monkeypatch.setenv(pspec.SPEC_ENV, "dp=2,tp=4")
        spec, source = pspec.resolve()
        assert source == "env" and spec.tp == 4

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv(pspec.SPEC_ENV, "dp=2")
        spec, source = pspec.resolve("dp=4;slices=2")
        assert source == "arg" and spec.slices == 2

    def test_explicit_parse_error_raises(self):
        with pytest.raises(pspec.SpecError):
            pspec.resolve("dp=banana")

    def test_env_parse_error_raises(self, monkeypatch):
        # A *declared* spec that cannot parse must be loud — silent
        # fallback would train on the wrong layout.
        monkeypatch.setenv(pspec.SPEC_ENV, "dp=0")
        with pytest.raises(pspec.SpecError):
            pspec.resolve()


# ----------------------------------------------------------------------
# hierarchical mesh: slice axis outermost, slice-aware batch helpers
# ----------------------------------------------------------------------

class TestHierarchicalMesh:
    def test_slice_axis_is_outermost(self):
        mesh = pspec.parse_spec(COMPOSED).make_mesh()
        assert mesh.axis_names[0] == mesh_lib.SLICE_AXIS
        assert dict(mesh.shape)[mesh_lib.SLICE_AXIS] == 2

    def test_single_slice_mesh_unchanged(self):
        # slices=1 must be byte-identical to the pre-pspec layout: no
        # slice axis at all, so every existing program re-lowers the
        # same HLO (the tier-1 safety property).
        mesh = pspec.parse_spec("dp=8").make_mesh()
        assert mesh_lib.SLICE_AXIS not in mesh.shape
        assert mesh.axis_names == mesh_lib.AXES

    def test_batch_axes_slice_aware(self):
        flat = pspec.parse_spec("dp=8").make_mesh()
        hier = pspec.parse_spec(COMPOSED).make_mesh()
        assert mesh_lib.batch_axes(flat) == mesh_lib.BATCH_AXES
        assert mesh_lib.batch_axes(hier) == (mesh_lib.SLICE_AXIS,
                                             *mesh_lib.BATCH_AXES)

    def test_data_parallel_size_counts_slices(self):
        flat = pspec.parse_spec("dp=4,fsdp=2").make_mesh()
        hier = pspec.parse_spec(COMPOSED).make_mesh()
        # batch shards over (slice, data, fsdp) — BATCH_AXES includes
        # fsdp (batch rides the weight shards), slice multiplies it
        assert mesh_lib.data_parallel_size(flat) == 8
        assert mesh_lib.data_parallel_size(hier) == 8

    def test_mesh_spec_slices_roundtrip(self):
        ms = pspec.parse_spec(COMPOSED).mesh_spec()
        assert ms.slices == 2
        assert ms.sizes(8)[mesh_lib.SLICE_AXIS] == 2


# ----------------------------------------------------------------------
# lowering onto the step seams
# ----------------------------------------------------------------------

class TestLower:
    def test_dp_lowering_is_shard_map_kwargs(self):
        spec = pspec.parse_spec("dp=8")
        mesh = spec.make_mesh()
        kw = pspec.lower(spec, mesh, weight_update="zero1",
                         fusion_threshold=1 << 20)
        assert kw["weight_update"] == "zero1"
        assert kw["fusion_threshold"] == 1 << 20
        assert kw["reduce_axes"] == mesh_lib.BATCH_AXES

    def test_hierarchical_dp_reduces_over_slice(self):
        spec = pspec.parse_spec("dp=4;slices=2")
        mesh = spec.make_mesh()
        kw = pspec.lower(spec, mesh)
        assert kw["reduce_axes"][0] == mesh_lib.SLICE_AXIS
        assert kw["batch_partition"] == P(mesh_lib.batch_axes(mesh))

    def test_weight_sharded_lowering_builds_shardings(self, mesh8):
        spec = pspec.parse_spec("dp=4,fsdp=2")
        mesh = spec.make_mesh()
        state = _tiny_lm_state(optax.adamw(1e-3))
        kw = pspec.lower(spec, mesh, state)
        assert "state_shardings" in kw

    def test_modifiers_refused_on_weight_sharded(self):
        spec = pspec.parse_spec("dp=4,fsdp=2")
        mesh = spec.make_mesh()
        with pytest.raises(pspec.SpecError, match="do not compose"):
            pspec.lower(spec, mesh, _tiny_lm_state(optax.adamw(1e-3)),
                        weight_update="zero1")

    def test_weight_sharded_needs_state(self):
        spec = pspec.parse_spec("dp=4,fsdp=2")
        mesh = spec.make_mesh()
        with pytest.raises(pspec.SpecError, match="TrainState"):
            pspec.lower(spec, mesh, None)

    def test_pp_refused(self):
        spec = pspec.parse_spec("dp=4,pp=2")
        mesh = spec.make_mesh()
        with pytest.raises(pspec.SpecError, match="pp_lm|harness"):
            pspec.lower(spec, mesh)

    def test_wrong_mesh_refused(self, mesh8):
        spec = pspec.parse_spec("dp=4,fsdp=2")
        with pytest.raises(pspec.SpecError, match="spec.make_mesh"):
            pspec.lower(spec, mesh8)  # mesh8 is data=8, fsdp=1

    def test_sp_lowering_widens_reduction_over_seq(self):
        spec = pspec.parse_spec("dp=4,sp=2")
        mesh = spec.make_mesh()
        kw = pspec.lower(spec, mesh)
        assert kw["reduce_axes"] == (*mesh_lib.batch_axes(mesh), "seq")
        assert kw["batch_partition"] == P(mesh_lib.batch_axes(mesh),
                                          "seq")

    def test_sp_refuses_shard_map_modifiers(self):
        spec = pspec.parse_spec("dp=4,sp=2")
        mesh = spec.make_mesh()
        for kw in ({"weight_update": "zero1"},
                   {"hier": "hier"},
                   {"fusion_threshold": 1 << 20},
                   {"grad_reduce": "adasum"}):
            with pytest.raises(pspec.SpecError, match="do not compose"):
                pspec.lower(spec, mesh, **kw)

    def test_tp_requires_rules(self):
        spec = pspec.parse_spec("dp=2,tp=4")
        mesh = spec.make_mesh()
        with pytest.raises(pspec.SpecError, match="tp_rules"):
            pspec.lower(spec, mesh, _tiny_lm_state(optax.adamw(1e-3)))

    def test_adasum_is_exclusive_but_lowers_alone(self):
        spec = pspec.parse_spec("dp=8")
        mesh = spec.make_mesh()
        with pytest.raises(pspec.SpecError, match="adasum"):
            pspec.lower(spec, mesh, weight_update="zero1",
                        grad_reduce="adasum")
        kw = pspec.lower(spec, mesh, grad_reduce="adasum")
        assert kw["grad_reduce"] == "adasum"

    def test_lower_pp_validates_before_delegating(self):
        nopp = pspec.parse_spec("dp=8")
        with pytest.raises(pspec.SpecError, match="pp > 1"):
            pspec.lower_pp(nopp, nopp.make_mesh(), None, None)
        comp = pspec.parse_spec("dp=2,tp=2,pp=2")
        with pytest.raises(pspec.SpecError, match="dp only"):
            pspec.lower_pp(comp, comp.make_mesh(), None, None)


# ----------------------------------------------------------------------
# golden-loss equivalence: spec-lowered vs hand-wired, 3 strategies
# ----------------------------------------------------------------------

N_GOLDEN_STEPS = 50


def _tiny_lm_pieces():
    from tpuframe import models

    model = models.get_model("transformer-lm", tiny=True, vocab_size=64,
                             max_seq=32)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 64, size=(8, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(ids[:2]))
    tx = optax.adamw(1e-3)

    def loss_fn(params, model_state, batch, rng):
        logits = model.apply({"params": params}, batch["input_ids"],
                             rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, batch["labels"]), (
            model_state, {})

    return variables, loss_fn, tx, {"input_ids": ids, "labels": labels}


def _tiny_lm_state(tx):
    variables, _, _, _ = _tiny_lm_pieces()
    return step_lib.TrainState.create(variables["params"], tx)


def _run_steps(step, state, batch, mesh, n_steps=N_GOLDEN_STEPS):
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh)), batch)
    out = []
    for _ in range(n_steps):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out, state


def _legacy_run(mesh, mode):
    variables, loss_fn, tx, batch = _tiny_lm_pieces()
    if mode == "fsdp":
        from tpuframe.parallel import fsdp as fsdp_lib

        state = step_lib.TrainState.create(variables["params"], tx)
        shardings = fsdp_lib.state_shardings(state, mesh)
        step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                        state_shardings=shardings)
        state = jax.tree.map(mesh_lib.host_device_put, state, shardings)
    elif mode == "zero1":
        state = zero1.make_state(variables["params"], tx, mesh)
        step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                        weight_update="zero1")
    else:
        state = step_lib.TrainState.create(variables["params"], tx)
        state = step_lib.replicate_state(state, mesh)
        step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False)
    return _run_steps(step, state, batch, mesh)


def _spec_run(spec_text, mode):
    variables, loss_fn, tx, batch = _tiny_lm_pieces()
    spec = pspec.parse_spec(spec_text)
    mesh = spec.make_mesh()
    state = step_lib.TrainState.create(variables["params"], tx)
    if mode == "zero1":
        state = zero1.make_state(variables["params"], tx, mesh)
        kw = pspec.lower(spec, mesh, weight_update="zero1")
    elif mode == "fsdp":
        kw = pspec.lower(spec, mesh, state)
        state = jax.tree.map(mesh_lib.host_device_put, state,
                             kw["state_shardings"])
    else:
        kw = pspec.lower(spec, mesh)
        state = step_lib.replicate_state(state, mesh)
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False, **kw)
    return _run_steps(step, state, batch, mesh)


@pytest.mark.slow
@pytest.mark.parametrize("spec_text,legacy_mesh_spec,mode", [
    ("dp=8", mesh_lib.MeshSpec(data=8), "replicated"),
    ("dp=8", mesh_lib.MeshSpec(data=8), "zero1"),
    ("dp=4,fsdp=2", mesh_lib.MeshSpec(data=4, fsdp=2), "fsdp"),
], ids=["dp", "dp-zero1", "fsdp"])
def test_golden_loss_spec_vs_legacy(spec_text, legacy_mesh_spec, mode):
    legacy_mesh = mesh_lib.make_mesh(legacy_mesh_spec)
    golden, gstate = _legacy_run(legacy_mesh, mode)
    got, sstate = _spec_run(spec_text, mode)
    np.testing.assert_allclose(got, golden, rtol=1e-5, atol=1e-6)
    assert golden[-1] < golden[0], "training should make progress"
    for a, b in zip(jax.tree.leaves(sstate.params),
                    jax.tree.leaves(gstate.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# the composed multi-slice strategy: detectors, pinned budget, DCN split
# ----------------------------------------------------------------------

class TestComposedStrategy:
    def test_registered(self):
        assert COMPOSED_NAME in strategies.STRATEGIES

    def test_register_spec_strategy_naming(self):
        name = strategies.register_spec_strategy(
            "dp=*", weight_update="zero1", hier="hier")
        try:
            assert name == "spec:dp=*+zero1+hier"
            assert name in strategies.STRATEGIES
        finally:
            strategies.STRATEGIES.pop(name, None)

    def test_wrong_world_size_is_unavailable(self):
        audit = strategies.audit_strategy(COMPOSED_NAME, n_devices=2)
        assert audit.status == "unavailable"

    @pytest.fixture(scope="class")
    def composed_audit(self):
        audit = strategies.audit_strategy(COMPOSED_NAME)
        if audit.status == "unavailable":
            pytest.skip(audit.reason)
        return audit

    def test_audit_ok(self, composed_audit):
        assert composed_audit.status == "ok", str(composed_audit.violations)
        assert dict(composed_audit.meta.mesh_shape)[
            mesh_lib.SLICE_AXIS] == 2

    def test_all_four_detectors_clean(self, composed_audit):
        flow = shardflow.audit_flow(composed_audit, n_devices=8)
        for det in ("redundant_pair", "wire_dtype", "replication",
                    "replica_groups"):
            assert flow["detectors"][det] == [], det

    def test_replica_groups_validate_against_slice_product(
            self, composed_audit):
        # The detector's valid sizes come from the declared hierarchical
        # mesh INCLUDING the slice axis: 2 (slice|data|fsdp), 4
        # (pairwise products), 8 (full product) all pass; corrupting the
        # declared slice size must produce findings.
        graph = cg.parse_graph(composed_audit.compiled.as_text())
        good = shardflow.detect_replica_groups(
            graph, composed_audit.meta.mesh_dict)
        assert good == []
        bad_mesh = dict(composed_audit.meta.mesh_dict)
        bad_mesh[mesh_lib.SLICE_AXIS] = 3
        assert shardflow.detect_replica_groups(graph, bad_mesh) != []

    def test_derived_budget_pinned_byte_exact(self, composed_audit):
        derived_file = shardflow.load_derived()
        assert derived_file is not None
        if derived_file["jax"] != jax.__version__:
            pytest.skip("derived_budgets.json pinned at another jax")
        pinned = shardflow.derived_for(COMPOSED_NAME)
        assert pinned is not None, (
            f"{COMPOSED_NAME} missing from derived_budgets.json — "
            f"run python -m tpuframe.analysis --emit-budgets")
        assert shardflow.derive_budget(
            composed_audit.report,
            composed_audit.budget.ignore_below) == pinned

    def test_dcn_split_nonzero_on_cross_slice_axis(self, composed_audit):
        flow = shardflow.audit_flow(composed_audit, n_devices=8)
        split = flow["comm_split"]
        assert split["slices"] == 2
        assert split["dcn_bytes"] > 0, "cross-slice traffic must price DCN"
        assert split["ici_bytes"] > 0, "in-slice traffic must price ICI"
        assert split["unattributed"] == 0
        assert split["ici_bytes"] + split["dcn_bytes"] == sum(
            split["ici"].values()) + sum(split["dcn"].values())

    def test_single_slice_strategy_has_no_dcn_bytes(self):
        audit = strategies.audit_strategy("dp")
        if audit.status == "unavailable":
            pytest.skip(audit.reason)
        split = shardflow.audit_flow(audit, n_devices=8)["comm_split"]
        assert split["slices"] == 1 and split["dcn_bytes"] == 0


# ----------------------------------------------------------------------
# iota replica-group materialization (the strided T(perm) forms the
# real fixtures contain — a contiguous-only reading would misattribute)
# ----------------------------------------------------------------------

class TestMaterializedGroups:
    def _node(self, text):
        graph = cg.parse_graph(f"""\
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}}

ENTRY %main (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %ar = f32[8]{{0}} all-reduce(f32[8]{{0}} %p0), {text}, to_apply=%add
}}
""")
        (_, node), = graph.collectives()
        return node

    @staticmethod
    def _as_lists(groups):
        return [list(g) for g in groups]

    def test_transposed_iota_is_strided(self):
        node = self._node("replica_groups=[2,4]<=[4,2]T(1,0)")
        groups = cg.materialized_groups(node, 8)
        assert self._as_lists(groups) == [[0, 2, 4, 6], [1, 3, 5, 7]]

    def test_plain_iota_is_contiguous(self):
        node = self._node("replica_groups=[2,4]<=[8]")
        groups = cg.materialized_groups(node, 8)
        assert self._as_lists(groups) == [[0, 1, 2, 3], [4, 5, 6, 7]]

    def test_explicit_groups_pass_through(self):
        node = self._node("replica_groups={{0,4},{1,5},{2,6},{3,7}}")
        groups = cg.materialized_groups(node, 8)
        assert self._as_lists(groups) == [[0, 4], [1, 5], [2, 6], [3, 7]]

    def test_inconsistent_spec_returns_none(self):
        node = self._node("replica_groups=[2,3]<=[8]")
        assert cg.materialized_groups(node, 8) is None


# ----------------------------------------------------------------------
# DCN roofline plane
# ----------------------------------------------------------------------

class TestDcnRoofline:
    def test_tables_clean(self):
        assert roofline.check_tables() == []

    def test_dcn_slower_than_ici_everywhere(self):
        for gen, hw in roofline.HARDWARE.items():
            assert 0 < hw.dcn_bytes_per_s < hw.ici_bytes_per_s, gen

    def test_dcn_ms_linear_in_bytes(self):
        a = roofline.dcn_ms("v5e", "all-reduce", 1 << 20, 2)
        b = roofline.dcn_ms("v5e", "all-reduce", 1 << 22, 2)
        assert b == pytest.approx(4 * a)

    def test_single_slice_is_free(self):
        assert roofline.dcn_ms("v5e", "all-reduce", 1 << 20, 1) == 0.0

    def test_comm_split_score_prices_both_fabrics(self):
        split = {"slices": 2, "ici": {"all-gather": 1 << 20},
                 "dcn": {"all-reduce": 1 << 20}}
        score = roofline.comm_split_score("v5e", split, n_devices=8,
                                          n_slices=2)
        fabrics = {r["fabric"] for r in score["rows"]}
        assert fabrics == {"ici", "dcn"}
        assert score["t_dcn_ms"] > score["t_ici_ms"]


# ----------------------------------------------------------------------
# TF119: the mesh-seam lint
# ----------------------------------------------------------------------

class TestTF119:
    RAW = ("from jax.sharding import Mesh\n"
           "m = Mesh(devs, ('data',))\n")

    def _lint(self, src, path):
        return [f for f in source_lint.lint_source(src, path)
                if f.rule == "TF119"]

    def test_raw_mesh_flagged(self):
        assert len(self._lint(self.RAW, "tpuframe/train.py")) == 1

    def test_dotted_spelling_flagged(self):
        src = "import jax\nm = jax.sharding.Mesh(devs, ('data',))\n"
        assert len(self._lint(src, "tpuframe/serve/engine.py")) == 1

    def test_jax_make_mesh_flagged(self):
        src = "import jax\nm = jax.make_mesh((8,), ('data',))\n"
        assert len(self._lint(src, "tpuframe/train.py")) == 1

    def test_seam_make_mesh_allowed(self):
        src = ("from tpuframe.parallel import mesh as mesh_lib\n"
               "m = mesh_lib.make_mesh(spec)\n")
        assert self._lint(src, "tpuframe/train.py") == []

    def test_mesh_seam_exempt(self):
        assert self._lint(self.RAW, "tpuframe/parallel/mesh.py") == []
        assert self._lint(self.RAW, "tpuframe/parallel/pspec.py") == []

    def test_suppression_honoured(self):
        src = ("from jax.sharding import Mesh\n"
               "m = Mesh(d, ('x',))  # tf-lint: ok[TF119]\n")
        assert self._lint(src, "tpuframe/train.py") == []

    def test_tree_is_clean(self):
        from pathlib import Path

        findings = [f for f in source_lint.lint_paths(
            [Path("tpuframe")]) if f.rule == "TF119"]
        assert findings == [], "\n".join(map(str, findings))


# ----------------------------------------------------------------------
# TF120: the strategy-registration seam lint
# ----------------------------------------------------------------------

class TestTF120:
    META = ("from tpuframe.analysis.strategies import StrategyMeta\n"
            "m = StrategyMeta(name='mine')\n")

    def _lint(self, src, path):
        return [f for f in source_lint.lint_source(src, path)
                if f.rule == "TF120"]

    def test_hand_built_meta_flagged(self):
        assert len(self._lint(self.META, "tpuframe/train.py")) == 1

    def test_registry_subscript_write_flagged(self):
        src = ("from tpuframe.analysis import strategies\n"
               "strategies.STRATEGIES['mine'] = build\n")
        assert len(self._lint(src, "tpuframe/bench.py")) == 1

    def test_registry_update_flagged(self):
        for call in ("STRATEGIES.update({'mine': build})",
                     "strategies.STRATEGIES.setdefault('mine', build)"):
            assert len(self._lint(call + "\n", "tpuframe/bench.py")) == 1

    def test_strategy_seam_exempt(self):
        assert self._lint(self.META,
                          "tpuframe/analysis/strategies.py") == []

    def test_reading_the_registry_is_fine(self):
        src = ("from tpuframe.analysis import strategies\n"
               "b = strategies.STRATEGIES['dp']\n"
               "names = list(strategies.STRATEGIES)\n")
        assert self._lint(src, "tpuframe/bench.py") == []

    def test_suppression_honoured(self):
        src = "m = StrategyMeta(name='x')  # tf-lint: ok[TF120]\n"
        assert self._lint(src, "tpuframe/train.py") == []

    def test_tree_is_clean(self):
        from pathlib import Path

        findings = [f for f in source_lint.lint_paths(
            [Path("tpuframe")]) if f.rule == "TF120"]
        assert findings == [], "\n".join(map(str, findings))


# ----------------------------------------------------------------------
# spec-lowered registration surface: aliases warn once, event registered
# ----------------------------------------------------------------------

class TestRegistration:
    def test_legacy_alias_warns_once(self):
        import warnings

        strategies._warned_legacy.discard("_build_zero1")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            try:
                strategies._build_zero1(8)
                strategies._build_zero1(8)
            except strategies.Unavailable:
                pass
        deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
        assert len(deps) == 1
        assert "spec-lowered" in str(deps[0].message)

    def test_dp_family_is_spec_lowered(self):
        import functools

        for name in ("dp", "dp-zero1", "dp-adasum"):
            builder = strategies.STRATEGIES[name]
            assert isinstance(builder, functools.partial)
            assert builder.func is strategies._build_from_spec

    def test_pspec_event_registered(self):
        from tpuframe.obs import events

        assert events.REQUIRED_FIELDS["pspec"] == ("spec", "source")

    def test_every_training_strategy_is_spec_lowered(self):
        """Tentpole acceptance: zero hand-wired training builders.  Every
        training entry in the registry is a partial over
        _build_from_spec with a spec string; serve-dp-decode is the one
        decode program (not a training parallelism, documented in the
        registry)."""
        import functools

        for name, builder in strategies.STRATEGIES.items():
            if name == "serve-dp-decode":
                continue
            assert isinstance(builder, functools.partial), name
            assert builder.func is strategies._build_from_spec, name
            assert builder.args and isinstance(builder.args[0], str), name
