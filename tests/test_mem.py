"""tpuframe.mem — the rematerialization policy registry (ISSUE PR 5).

Golden invariant: every policy is a *schedule* decision, never a numeric
one — wrapping the loss in ``jax.checkpoint`` under any saveable
predicate must reproduce the ``none`` losses step for step (recompute
replays the identical forward ops).  The searched winner can then be
applied from the tuning DB without re-validating training math.

Also pinned here: env/DB resolution precedence (explicit env > legacy
alias > tune_db > default), the legacy ``TPUFRAME_BENCH_REMAT`` fold-in,
the donation audit over compiled HLO alias tables, the TF108 lint that
keeps bare remat out of model/step code, the bytes-MFU (HBM-roofline
utilization) math, and the ``(tag, policy)`` keying of the offline A/B
parser."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe import mem
from tpuframe.mem import policy as mem_policy
from tpuframe.models import losses, resnet
from tpuframe.parallel import mesh as mesh_lib, step as step_lib


# ----------------------------------------------------------------------
# policy registry
# ----------------------------------------------------------------------

class TestPolicyRegistry:
    def test_presets_registered(self):
        pols = mem.available_policies()
        for p in ("none", "everything", "dots", "dots_no_batch",
                  "per_block", "full"):
            assert p in pols

    def test_validate_accepts_presets_and_save_named(self):
        for p in mem.available_policies():
            assert mem.validate_policy(p) == p
        assert (mem.validate_policy("save_named(block_out)")
                == "save_named(block_out)")

    def test_validate_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown remat policy"):
            mem.validate_policy("per_blok")

    def test_parse_save_named_round_trip(self):
        names = mem.parse_save_named("save_named(stem_out, block_out)")
        assert names == ("stem_out", "block_out")
        for n in names:
            assert n in mem.SEAM_NAMES

    def test_parse_save_named_rejects_unknown_seam(self):
        with pytest.raises(ValueError, match="unknown seam"):
            mem.parse_save_named("save_named(bogus_seam)")

    def test_parse_save_named_rejects_empty(self):
        with pytest.raises(ValueError):
            mem.parse_save_named("save_named()")

    def test_wrap_none_is_identity(self):
        def f(x):
            return x * 2
        assert mem.wrap(f, "none") is f
        assert mem.wrap(f, None) is f
        assert mem.wrap(f, "per_block") is not f

    def test_self_check_clean(self):
        # the registry's own gate (also run by the analysis CI gate):
        # every preset applies, parse round-trips, and the annotated
        # model/step files carry no bare remat.
        assert mem.check() == []


# ----------------------------------------------------------------------
# golden-loss equivalence: every policy reproduces the `none` training
# trajectory (8 virtual CPU devices, real ResNet blocks so the named
# seams exist)
# ----------------------------------------------------------------------

def _tiny_resnet_losses(mesh, remat_policy, n_steps=2):
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

    step = step_lib.make_train_step(
        loss_fn, tx, mesh, donate=False,
        remat_policy=None if remat_policy == "none" else remat_policy)
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
    return out


@pytest.fixture(scope="module")
def golden_losses(mesh8):
    return _tiny_resnet_losses(mesh8, "none")


@pytest.mark.parametrize("policy", [
    "everything", "dots", "dots_no_batch", "per_block", "full",
    "save_named(block_out)",
])
def test_golden_loss_equivalence(mesh8, golden_losses, policy):
    got = _tiny_resnet_losses(mesh8, policy)
    np.testing.assert_allclose(got, golden_losses, rtol=1e-5, atol=1e-6)
    assert golden_losses[-1] < golden_losses[0]


# ----------------------------------------------------------------------
# env / tuning-DB resolution
# ----------------------------------------------------------------------

@pytest.fixture
def clean_env(monkeypatch):
    for var in ("TPUFRAME_REMAT_POLICY", "TPUFRAME_BENCH_REMAT",
                "TPUFRAME_TUNE_DB", "TPUFRAME_TUNE_GEN"):
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


class TestEnvResolution:
    def test_explicit_env_wins(self, clean_env):
        clean_env.setenv("TPUFRAME_REMAT_POLICY", "dots")
        clean_env.setenv("TPUFRAME_BENCH_REMAT", "1")
        assert mem.policy_from_env() == "dots"
        assert mem.resolve() == ("dots", "env")

    def test_explicit_env_validated(self, clean_env):
        clean_env.setenv("TPUFRAME_REMAT_POLICY", "nope")
        with pytest.raises(ValueError, match="unknown remat policy"):
            mem.policy_from_env()

    def test_legacy_alias_maps_to_per_block(self, clean_env, capsys):
        clean_env.setenv("TPUFRAME_BENCH_REMAT", "1")
        mem_policy._warned_legacy = False
        assert mem.policy_from_env() == "per_block"
        assert "deprecated" in capsys.readouterr().out
        # warn-once: the second read is silent
        assert mem.policy_from_env() == "per_block"
        assert "deprecated" not in capsys.readouterr().out
        assert mem.resolve() == ("per_block", "env_legacy")

    def test_legacy_zero_is_unset(self, clean_env):
        clean_env.setenv("TPUFRAME_BENCH_REMAT", "0")
        assert mem.policy_from_env() is None

    def test_default_without_env_or_db(self, clean_env):
        clean_env.setenv("TPUFRAME_TUNE_DB", "off")
        assert mem.resolve(program="train_resnet50_b512",
                           family="remat_resnet50") == ("none", "default")


def _seed_remat_db(path):
    from tpuframe.tune import db as tune_db
    db = tune_db.TuningDB(str(path))
    for pol, ms in (("none", 177.2), ("per_block", 150.0)):
        db.add({"program": "train_resnet50_b512",
                "family": "remat_resnet50",
                "fingerprint": "fp-test",
                "topology": "v5e:2x2",
                "generation": "v5e",
                "config": {"remat_policy": pol, "batch": 512},
                "predicted": {"predicted_ms": ms}})
    db.save()
    return db


class TestTuneDBResolution:
    def test_db_round_trip_and_best(self, tmp_path):
        from tpuframe.tune import db as tune_db
        path = tmp_path / "tune_db.json"
        _seed_remat_db(path)
        reloaded = tune_db.TuningDB.open(str(path))
        assert tune_db.validate(reloaded.data) == []
        best = reloaded.best(family="remat_resnet50", generation="v5e")
        assert best.config["remat_policy"] == "per_block"

    def test_resolve_consults_db(self, clean_env, tmp_path):
        path = tmp_path / "tune_db.json"
        _seed_remat_db(path)
        clean_env.setenv("TPUFRAME_TUNE_DB", str(path))
        clean_env.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert mem.resolve(program="train_resnet50_b512",
                           family="remat_resnet50") == ("per_block",
                                                        "tune_db")

    def test_db_gated_on_generation(self, clean_env, tmp_path):
        # no target generation (the CPU test-run case) -> hard default,
        # never a TPU-searched policy
        path = tmp_path / "tune_db.json"
        _seed_remat_db(path)
        clean_env.setenv("TPUFRAME_TUNE_DB", str(path))
        assert mem.resolve(program="train_resnet50_b512",
                           family="remat_resnet50") == ("none", "default")

    def test_env_preempts_db(self, clean_env, tmp_path):
        from tpuframe.tune import db as tune_db
        path = tmp_path / "tune_db.json"
        _seed_remat_db(path)
        clean_env.setenv("TPUFRAME_TUNE_DB", str(path))
        clean_env.setenv("TPUFRAME_TUNE_GEN", "v5e")
        clean_env.setenv("TPUFRAME_REMAT_POLICY", "dots")
        assert mem.resolve(program="train_resnet50_b512",
                           family="remat_resnet50") == ("dots", "env")
        # and the DB-side helper refuses to shadow an env override
        assert tune_db.resolve_remat_policy("train_resnet50_b512") is None

    def test_record_env_overrides_include_policy(self, tmp_path):
        from tpuframe.tune import db as tune_db
        path = tmp_path / "tune_db.json"
        db = _seed_remat_db(path)
        rec = db.best(family="remat_resnet50")
        env = rec.env_overrides()
        assert env["TPUFRAME_REMAT_POLICY"] == "per_block"


# ----------------------------------------------------------------------
# donation / aliasing audit
# ----------------------------------------------------------------------

class TestDonationAudit:
    def _compile(self, donate):
        def f(state, batch):
            return jax.tree.map(lambda a: a + jnp.sum(batch), state)
        state = {"w": jnp.zeros((64, 64)), "m": jnp.zeros((64, 64))}
        batch = jnp.ones((8,))
        fn = (jax.jit(f, donate_argnums=(0,)) if donate else jax.jit(f))
        return fn.lower(state, batch).compile()

    def test_donated_step_passes(self):
        compiled = self._compile(donate=True)
        rep = mem.donation_report(compiled)
        assert rep["donated"]
        assert rep["n_aliased"] >= 2           # both state leaves
        assert 0 in rep["aliased_params"]
        assert mem.audit_step_donation(compiled) == []

    def test_undonated_step_flagged(self):
        compiled = self._compile(donate=False)
        rep = mem.donation_report(compiled)
        assert not rep["donated"]
        problems = mem.audit_step_donation(compiled)
        assert problems and "no input_output_alias entries" in problems[0]


# ----------------------------------------------------------------------
# TF108: bare remat stays out of model/step code
# ----------------------------------------------------------------------

class TestTF108:
    def _rules(self, src, path):
        from tpuframe.analysis import source_lint
        return [f.rule for f in source_lint.lint_source(src, path)]

    BARE = ("import jax\n"
            "def f(x):\n"
            "    return jax.checkpoint(lambda y: y * 2)(x)\n")

    def test_flags_bare_checkpoint_in_models(self):
        assert "TF108" in self._rules(self.BARE, "tpuframe/models/net.py")
        assert "TF108" in self._rules(
            "import jax\ndef f(g, x):\n    return jax.remat(g)(x)\n",
            "tpuframe/parallel/step2.py")

    def test_registry_itself_exempt(self):
        assert "TF108" not in self._rules(self.BARE, "tpuframe/mem/policy.py")

    def test_out_of_scope_path_exempt(self):
        assert "TF108" not in self._rules(self.BARE, "tpuframe/obs/x.py")

    def test_suppression_comment(self):
        src = ("import jax\n"
               "def f(x):\n"
               "    return jax.checkpoint(lambda y: y * 2)(x)"
               "  # tf-lint: ok[TF108]\n")
        assert "TF108" not in self._rules(src, "tpuframe/models/net.py")

    def test_shipped_model_and_step_code_clean(self):
        # the actual annotated files route everything through mem.*
        from tpuframe.analysis import source_lint
        import tpuframe
        import os
        root = os.path.dirname(tpuframe.__file__)
        paths = [os.path.join(root, "models", "resnet.py"),
                 os.path.join(root, "models", "transformer_lm.py"),
                 os.path.join(root, "parallel", "step.py"),
                 os.path.join(root, "parallel", "pp_lm.py")]
        findings = [f for f in source_lint.lint_paths(paths)
                    if f.rule == "TF108"]
        assert findings == []


# ----------------------------------------------------------------------
# obs: bytes-MFU (HBM-roofline utilization) + remat_policy run event
# ----------------------------------------------------------------------

class TestHbmUtil:
    def test_math(self):
        from tpuframe.obs import goodput
        from tpuframe.tune import roofline
        hw = roofline.HARDWARE["v5e"]
        # one device streaming exactly its bandwidth for 1s -> 100%
        assert goodput.hbm_util(hw.hbm_bytes_per_s, 1.0,
                                generation="v5e") == pytest.approx(1.0)
        # PERF §2 anchor: 143.5 GB over the 177.2ms roofline step = 100%
        assert goodput.hbm_util(1.435e11, 0.1772,
                                generation="v5e") == pytest.approx(1.0,
                                                                   rel=1e-3)
        assert goodput.hbm_util(0.0, 1.0, generation="v5e") == 0.0
        assert goodput.hbm_util(1.0, 0.0, generation="v5e") == 0.0

    def test_from_events_recompute(self):
        from tpuframe.obs import goodput
        from tpuframe.tune import roofline
        hw = roofline.HARDWARE["v5e"]
        t0 = 1000.0
        events = [
            {"type": "run_start", "t": t0, "step": 0,
             "bytes_per_step": hw.hbm_bytes_per_s * 0.1},
            # first step is the compile and is excluded from the mean
            {"type": "step", "t": t0 + 1, "step": 1, "wall_ms": 9000.0},
            {"type": "step", "t": t0 + 2, "step": 2, "wall_ms": 100.0},
            {"type": "step", "t": t0 + 3, "step": 3, "wall_ms": 100.0},
        ]
        out = goodput.from_events(events, generation="v5e")
        assert out["hbm_util_productive"] == pytest.approx(1.0, rel=1e-6)

    def test_from_events_run_end_passthrough(self):
        from tpuframe.obs import goodput
        events = [
            {"type": "run_start", "t": 0.0, "step": 0},
            {"type": "run_end", "t": 10.0, "step": 5, "outcome": "ok",
             "hbm_util_productive": 0.81},
        ]
        out = goodput.from_events(events, generation="v5e")
        assert out["hbm_util_productive"] == pytest.approx(0.81)


class TestRematPolicyEvent:
    def test_schema_registered(self):
        from tpuframe.obs import events
        assert events.REQUIRED_FIELDS["remat_policy"] == ("policy",
                                                          "source")

    def test_validate_record(self):
        from tpuframe.obs import events
        good = {"schema": events.SCHEMA_VERSION, "type": "remat_policy",
                "t": 1.0, "host": "h", "proc": 0, "attempt": 0,
                "policy": "per_block", "source": "tune_db",
                "predicted_bytes_per_step": 1.7e11}
        assert events.validate_record(good) == []
        bad = dict(good)
        del bad["source"]
        assert any("source" in p for p in events.validate_record(bad))


# ----------------------------------------------------------------------
# offline A/B parser: (tag, policy) keying
# ----------------------------------------------------------------------

class TestAbRowsPolicyColumn:
    def test_policies_coexist_under_one_tag(self):
        from perf import _ab_rows
        lines = [
            json.dumps({"tag": "resnet50_remat_b512", "policy": "none",
                        "gb": 143.5}),
            json.dumps({"tag": "resnet50_remat_b512", "policy": "per_block",
                        "gb": 170.8}),
            json.dumps({"tag": "resnet50_b512", "gb": 143.5}),
        ]
        rows = _ab_rows.parse_rows(lines)
        assert len(rows) == 3
        assert _ab_rows.superseded_count(lines) == 0

    def test_same_policy_supersedes(self):
        from perf import _ab_rows
        lines = [
            json.dumps({"tag": "t", "policy": "dots", "gb": 1.0}),
            json.dumps({"tag": "t", "policy": "dots", "gb": 2.0}),
            json.dumps({"tag": "t", "gb": 9.0}),  # (t, None) is distinct
        ]
        rows = _ab_rows.parse_rows(lines)
        assert len(rows) == 2
        assert rows[0]["gb"] == 2.0
        assert _ab_rows.superseded_count(lines) == 1


# ----------------------------------------------------------------------
# sweep candidate list sanity (the TPU compile itself is tier-slow, in
# test_aot_tpu_compile.py)
# ----------------------------------------------------------------------

def test_remat_sweep_candidates_are_valid_policies():
    from tpuframe.tune import search
    cands = search.remat_policy_candidates()
    assert "none" in cands and "per_block" in cands
    for pol in cands:
        mem.validate_policy(pol)
    # `everything` is deliberately absent: byte-identical to `none`
    assert "everything" not in cands
