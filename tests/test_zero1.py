"""tpuframe.parallel.zero1 — ZeRO-1 weight-update sharding (ISSUE PR 7).

Golden invariants pinned here:

* the sharded update is a *layout* decision, never a numeric one —
  ``weight_update="zero1"`` must reproduce the replicated trajectory step
  for step (reduce-scatter(mean) feeds the same global mean gradient to
  the same element-wise update math);
* the collective swap is proven at the wire level: the ``dp-zero1``
  strategy audit must show reduce-scatter + all-gather at EXACTLY the
  pad-to-multiple byte total and no gradient all-reduce above the scalar
  floor;
* the reduce-scatter / all-gather pair round-trips (including the
  gradient transpose, which is how the step's backward actually runs
  them), and non-divisible shards are rejected with a message naming the
  pad-to-multiple fix;
* resolution precedence (env > generation-gated tune DB > replicated
  default) and the fail-open contract: a stale or bogus DB row must
  never break a run;
* TF110 keeps stray optimizer updates out of the harness/parallel tree
  so nothing bypasses the weight-update seam.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpuframe.analysis import budgets as budgets_lib
from tpuframe.analysis import source_lint, strategies
from tpuframe.models import losses, resnet
from tpuframe.obs import events
from tpuframe.parallel import collectives
from tpuframe.parallel import mesh as mesh_lib
from tpuframe.parallel import step as step_lib
from tpuframe.parallel import zero1
from tpuframe.parallel.step import _shard_map
from tpuframe.tune import db as tune_db


# ----------------------------------------------------------------------
# pad-to-multiple layout arithmetic
# ----------------------------------------------------------------------

class TestPadLayout:
    def test_padded_rounds_up_to_multiple(self):
        assert zero1._padded(16, 8) == 16
        assert zero1._padded(17, 8) == 24
        assert zero1._padded(1, 8) == 8
        assert zero1._padded(0, 8) == 0

    def test_padded_bytes_counts_the_padding(self):
        probe = {"w": jax.ShapeDtypeStruct((3, 5), jnp.float32),
                 "b": jax.ShapeDtypeStruct((7,), jnp.float32)}
        # 15 -> 16, 7 -> 8 elements, 4 bytes each
        assert zero1.padded_bytes(probe, 8) == (16 + 8) * 4

    def test_padding_census_self_consistent(self):
        probe = {"w": jax.ShapeDtypeStruct((3, 5), jnp.float32),
                 "b": jax.ShapeDtypeStruct((7,), jnp.bfloat16)}
        census = zero1.padding_census(probe, 8)
        assert census["n_shards"] == 8
        assert len(census["leaves"]) == 2
        for row in census["leaves"]:
            assert row["padded"] % 8 == 0
            assert row["pad_waste"] == row["padded"] - row["size"]
        assert census["padded_elems"] >= census["total_elems"]
        assert census["padded_bytes"] == zero1.padded_bytes(probe, 8)
        assert census["waste_frac"] == pytest.approx(
            (census["padded_elems"] - census["total_elems"])
            / census["total_elems"])

    def test_self_check_clean(self):
        assert zero1.check() == []


# ----------------------------------------------------------------------
# reduce-scatter / all-gather round trip (the wire pattern itself)
# ----------------------------------------------------------------------

class TestCollectivesRoundTrip:
    def test_scatter_gather_identity(self, mesh8):
        x = jnp.arange(16, dtype=jnp.float32)

        def f(x):
            shard = collectives.reduce_scatter(x, "data", average=True)
            assert shard.shape == (2,)
            return collectives.allgather(shard, "data", tiled=True)

        out = jax.jit(_shard_map(f, mesh=mesh8, in_specs=P(),
                                 out_specs=P()))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_scatter_sums_without_average(self, mesh8):
        x = jnp.ones((8,), jnp.float32)

        def f(x):
            return collectives.allgather(
                collectives.reduce_scatter(x, "data", average=False),
                "data", tiled=True)

        out = jax.jit(_shard_map(f, mesh=mesh8, in_specs=P(),
                                 out_specs=P()))(x)
        np.testing.assert_array_equal(np.asarray(out), np.full((8,), 8.0))

    def test_non_divisible_rejected_with_padding_hint(self, mesh8):
        x = jnp.arange(10, dtype=jnp.float32)

        def f(x):
            return collectives.reduce_scatter(x, "data")

        with pytest.raises(ValueError, match="pad-to-multiple"):
            jax.jit(_shard_map(f, mesh=mesh8, in_specs=P(),
                               out_specs=P("data")))(x)

    def test_grad_transposes_through_the_pair(self, mesh8):
        # The step's backward differentiates THROUGH the scatter/gather
        # pair (psum_scatter transposes to all_gather and vice versa);
        # loss = sum(gather(scatter(x, mean))) == sum(x), so d/dx = 1.
        x = jnp.arange(16, dtype=jnp.float32)

        def loss(x):
            def f(x):
                shard = collectives.reduce_scatter(x, "data", average=True)
                full = collectives.allgather(shard, "data", tiled=True)
                return jnp.sum(full)

            per_replica = _shard_map(f, mesh=mesh8, in_specs=P(),
                                     out_specs=P())
            return per_replica(x)

        g = jax.grad(loss)(x)
        np.testing.assert_allclose(np.asarray(g), np.ones(16), rtol=1e-6)


# ----------------------------------------------------------------------
# sharded state construction
# ----------------------------------------------------------------------

def _toy_params():
    return {"w": jnp.ones((3, 5), jnp.float32),
            "b": jnp.zeros((7,), jnp.float32)}


class TestStateLayout:
    def test_init_opt_state_is_flat_padded(self):
        tx = optax.adamw(1e-3)
        opt = zero1.init_opt_state(tx, _toy_params(), 8)
        dims = {leaf.shape for leaf in jax.tree.leaves(opt)
                if getattr(leaf, "ndim", 0) >= 1}
        assert dims == {(16,), (8,)}  # 15 -> 16, 7 -> 8

    def test_make_state_passes_layout_check(self, mesh8):
        tx = optax.adamw(1e-3)
        state = zero1.make_state(_toy_params(), tx, mesh8)
        n = zero1.world_size(mesh8)
        assert n == 8
        assert zero1.check_state_layout(state, n) is state

    def test_make_state_shards_the_moments(self, mesh8):
        tx = optax.sgd(0.1, momentum=0.9)
        state = zero1.make_state(_toy_params(), tx, mesh8)
        for leaf in jax.tree.leaves(state.opt_state):
            if getattr(leaf, "ndim", 0) >= 1:
                shards = leaf.sharding.shard_shape(leaf.shape)
                assert shards[0] == leaf.shape[0] // 8

    def test_replicated_state_rejected(self, mesh8):
        tx = optax.adamw(1e-3)
        state = step_lib.TrainState.create(_toy_params(), tx)
        with pytest.raises(ValueError, match="zero1.make_state"):
            zero1.check_state_layout(state, 8)

    def test_world_of_one_degenerates_to_replicated_update(self):
        tx = optax.sgd(0.1, momentum=0.9)
        params = _toy_params()
        grads = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)
        opt = tx.init(params)
        new_p, _, norm = zero1.sharded_update(tx, (), params, opt, grads)
        updates, _ = tx.update(grads, tx.init(params), params)
        want = optax.apply_updates(params, updates)
        for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(float(norm),
                                   float(optax.global_norm(grads)),
                                   rtol=1e-6)


# ----------------------------------------------------------------------
# golden-loss equivalence: zero1 reproduces the replicated trajectory
# ----------------------------------------------------------------------

N_GOLDEN_STEPS = 50


def _resnet_run(mesh, weight_update, n_steps=N_GOLDEN_STEPS):
    """test_mem's tiny-ResNet recipe (batch_stats exercise the
    model_state path) under either weight-update mode."""
    model = resnet.ResNet(stage_sizes=(1, 1), block_cls=resnet.BasicBlock,
                          num_classes=4, width=8, cifar_stem=True)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(x[:2]))
    tx = optax.sgd(0.1, momentum=0.9)

    def loss_fn(params, model_state, batch, rng):
        logits, mut = model.apply({"params": params, **model_state},
                                  batch["x"], train=True,
                                  mutable=["batch_stats"])
        return losses.softmax_cross_entropy(logits, batch["y"]), (
            dict(mut), {})

    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                    weight_update=weight_update)
    if weight_update == "zero1":
        state = zero1.make_state(
            variables["params"], tx, mesh,
            model_state={"batch_stats": variables["batch_stats"]})
    else:
        state = step_lib.TrainState.create(
            variables["params"], tx,
            model_state={"batch_stats": variables["batch_stats"]})
        state = step_lib.replicate_state(state, mesh)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh)),
        {"x": x, "y": y})
    out = []
    for _ in range(n_steps):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out, state


def _lm_run(mesh, weight_update, n_steps=N_GOLDEN_STEPS):
    """Tiny TransformerLM under adamw — the second optimizer family
    (adam moments, not just sgd momentum) and the dict-batch LM path."""
    from tpuframe import models

    model = models.get_model("transformer-lm", tiny=True, vocab_size=64,
                             max_seq=32)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 64, size=(8, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(ids[:2]))
    tx = optax.adamw(1e-3)

    def loss_fn(params, model_state, batch, rng):
        logits = model.apply({"params": params}, batch["input_ids"],
                             rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, batch["labels"]), (
            model_state, {})

    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                    weight_update=weight_update)
    if weight_update == "zero1":
        state = zero1.make_state(variables["params"], tx, mesh)
    else:
        state = step_lib.TrainState.create(variables["params"], tx)
        state = step_lib.replicate_state(state, mesh)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.batch_sharding(mesh)),
        {"input_ids": ids, "labels": labels})
    out = []
    for _ in range(n_steps):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out, state


@pytest.mark.parametrize("runner", [_resnet_run, _lm_run],
                         ids=["resnet-sgd-momentum", "lm-adamw"])
def test_golden_loss_equivalence(mesh8, runner):
    golden, gstate = runner(mesh8, "replicated")
    got, zstate = runner(mesh8, "zero1")
    np.testing.assert_allclose(got, golden, rtol=1e-5, atol=1e-6)
    assert golden[-1] < golden[0], "training should make progress"
    # final params match too — the trajectories are identical, not
    # merely loss-similar
    for a, b in zip(jax.tree.leaves(zstate.params),
                    jax.tree.leaves(gstate.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# the wire-level proof: dp-zero1 strategy audit
# ----------------------------------------------------------------------

class TestAudit:
    def test_dp_zero1_registered(self):
        assert "dp-zero1" in strategies.STRATEGIES
        b = budgets_lib.strategy_budget("dp-zero1",
                                        padded_param_bytes=4096)
        assert b.allowed == {"reduce-scatter": 4096, "all-gather": 4096}

    def test_collective_swap_is_exact(self):
        audit = strategies.audit_strategy("dp-zero1")
        if audit.status == "unavailable":
            pytest.skip(audit.reason)
        assert audit.status == "ok", str(audit.violations)
        kinds = audit.report.bytes_by_kind()
        budget = audit.budget
        # grads in / params out at EXACTLY the pad-to-multiple total
        assert kinds.get("reduce-scatter") == \
            budget.allowed["reduce-scatter"]
        assert kinds.get("all-gather") == budget.allowed["all-gather"]
        # the defect class itself: any gradient all-reduce above the
        # scalar floor means the swap did not happen
        assert audit.report.bytes_by_kind(
            min_bytes=budget.ignore_below).get("all-reduce", 0) == 0
        # and the checked-in auto-derived budget IS this program's
        # record — no hand-copied byte constants to fall out of date
        # (python -m tpuframe.analysis --emit-budgets regenerates it)
        from tpuframe.analysis import shardflow

        derived_file = shardflow.load_derived()
        assert derived_file is not None
        if derived_file["jax"] == jax.__version__:
            assert shardflow.derive_budget(
                audit.report, budget.ignore_below) == \
                shardflow.derived_for("dp-zero1")

    def test_budget_is_exact_padded_bytes(self):
        b = budgets_lib.zero1_budget(1000)
        assert b.allowed == {"reduce-scatter": 1000, "all-gather": 1000}
        assert b.ignore_below == 1024


# ----------------------------------------------------------------------
# resolution precedence: env > tune DB (generation-gated) > default
# ----------------------------------------------------------------------

class TestResolution:
    @pytest.fixture(autouse=True)
    def clean_env(self, monkeypatch):
        monkeypatch.delenv(zero1.ENV_VAR, raising=False)
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
        monkeypatch.setenv("TPUFRAME_TUNE_DB", "off")

    @pytest.fixture
    def seeded_db(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add({"program": "train_resnet50_b512",
                "family": "weight_update_resnet50",
                "fingerprint": "fp0", "topology": "v5e:2x2",
                "generation": "v5e",
                "config": {"weight_update": "zero1", "batch": 512},
                "predicted": {"predicted_ms": 5.0, "bound": "hbm",
                              "fits": True, "vmem_bytes": 0,
                              "bytes_lower_bound": True}})
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        return db

    def test_default_is_replicated(self):
        assert zero1.resolve() == ("replicated", "default")

    def test_env_override_wins(self, monkeypatch, seeded_db):
        monkeypatch.setenv(zero1.ENV_VAR, "zero1")
        assert zero1.resolve(program="anything") == ("zero1", "env")
        monkeypatch.setenv(zero1.ENV_VAR, "replicated")
        assert zero1.resolve(program="train_resnet50_b512") == \
            ("replicated", "env")

    def test_env_bogus_mode_raises(self, monkeypatch):
        monkeypatch.setenv(zero1.ENV_VAR, "zero2")
        with pytest.raises(ValueError, match="unknown weight-update mode"):
            zero1.resolve()

    def test_db_winner_engages_with_generation(self, seeded_db,
                                               monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert zero1.resolve(program="train_resnet50_b512") == \
            ("zero1", "tune_db")
        # family fallback for a program the sweep never compiled verbatim
        assert zero1.resolve(program="train_resnet50_b1024",
                             family="weight_update_resnet50") == \
            ("zero1", "tune_db")

    def test_no_generation_means_default(self, seeded_db):
        # the tier-1 guarantee: CPU runs never see DB layout decisions
        assert zero1.resolve(program="train_resnet50_b512") == \
            ("replicated", "default")

    def test_stale_db_mode_falls_back(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add({"program": "train_resnet50_b512",
                "family": "weight_update_resnet50",
                "fingerprint": "fp0", "topology": "v5e:2x2",
                "generation": "v5e",
                "config": {"weight_update": "zero9"},
                "predicted": {"predicted_ms": 5.0, "bound": "hbm",
                              "fits": True, "vmem_bytes": 0,
                              "bytes_lower_bound": True}})
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        # a stale/bogus DB row must never break a run
        assert zero1.resolve(program="train_resnet50_b512") == \
            ("replicated", "default")

    def test_validate_mode(self):
        assert zero1.validate_mode("ZERO1") == "zero1"
        assert zero1.validate_mode("") == "replicated"
        with pytest.raises(ValueError, match="TPUFRAME_WEIGHT_UPDATE"):
            zero1.validate_mode("fsdp")


# ----------------------------------------------------------------------
# step-builder guard rails
# ----------------------------------------------------------------------

class TestStepGuards:
    def _loss(self, params, model_state, batch, rng):
        return jnp.sum(params["w"] * batch["x"]), (model_state, {})

    def test_zero1_requires_mesh(self):
        with pytest.raises(ValueError, match="needs a mesh"):
            step_lib.make_train_step(self._loss, optax.sgd(0.1), None,
                                     weight_update="zero1")

    def test_zero1_rejects_adasum(self, mesh8):
        with pytest.raises(ValueError, match="zero1"):
            step_lib.make_train_step(self._loss, optax.sgd(0.1), mesh8,
                                     grad_reduce="adasum",
                                     weight_update="zero1")

    def test_unknown_mode_rejected(self, mesh8):
        with pytest.raises(ValueError, match="unknown weight_update"):
            step_lib.make_train_step(self._loss, optax.sgd(0.1), mesh8,
                                     weight_update="zero3")


# ----------------------------------------------------------------------
# TF110: optimizer updates stay at the weight-update seam
# ----------------------------------------------------------------------

def _lint_file(tmp_path, rel, src):
    f = tmp_path / rel
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(src)
    return [x for x in source_lint.lint_paths([f]) if x.rule == "TF110"]


_STRAY_UPDATE = """
def step(tx, grads, opt_state, params):
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state
"""


class TestTF110:
    def test_fires_in_parallel_scope(self, tmp_path):
        found = _lint_file(tmp_path, "parallel/rogue.py", _STRAY_UPDATE)
        assert len(found) == 2
        assert all(f.rule == "TF110" for f in found)

    def test_fires_in_train_py(self, tmp_path):
        assert _lint_file(tmp_path, "train.py", _STRAY_UPDATE)

    def test_silent_outside_scope(self, tmp_path):
        assert _lint_file(tmp_path, "models/rogue.py", _STRAY_UPDATE) == []

    def test_seam_files_exempt(self, tmp_path):
        assert _lint_file(tmp_path, "parallel/step.py", _STRAY_UPDATE) == []
        assert _lint_file(tmp_path, "parallel/zero1.py",
                          _STRAY_UPDATE) == []

    def test_dict_update_not_flagged(self, tmp_path):
        src = "def f(d, cfg):\n    d.update(cfg, x=1)\n    return d\n"
        assert _lint_file(tmp_path, "parallel/cfgs.py", src) == []

    def test_suppression_honored(self, tmp_path):
        src = _STRAY_UPDATE.replace(
            "tx.update(grads, opt_state, params)",
            "tx.update(grads, opt_state, params)  # tf-lint: ok[TF110]"
        ).replace(
            "optax.apply_updates(params, updates)",
            "optax.apply_updates(params, updates)  # tf-lint: ok[TF110]")
        assert _lint_file(tmp_path, "parallel/rogue.py", src) == []

    def test_shipped_seam_files_clean(self):
        assert zero1.check() == []


# ----------------------------------------------------------------------
# observability: the weight_update run event
# ----------------------------------------------------------------------

class TestWeightUpdateEvent:
    def test_schema_registered(self):
        assert events.REQUIRED_FIELDS["weight_update"] == ("mode", "source")

    def test_emitted_record_validates(self, tmp_path):
        with events.EventLog(str(tmp_path)) as log:
            rec = log.emit("weight_update", mode="zero1", source="env",
                           n_shards=8)
        assert rec is not None
        assert events.validate_record(rec) == []
        (path,) = events.event_files(str(tmp_path))
        (read,) = events.read_file(path)
        assert read["mode"] == "zero1" and read["n_shards"] == 8
