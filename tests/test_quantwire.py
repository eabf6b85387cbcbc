"""The wire-format seam end to end: resolution precedence (env > tuning
DB > default, stale rows demote silently), TF115 seam lint, shardflow
registration + seeded positive, derived-budget byte ratios for the int8
strategies, and golden-loss parity of the int8 wire against fp for both
weight-update modes.

Numerics use the legacy ``jax.experimental.shard_map`` idiom
(``check_rep=False``) so the suite runs on pre-vma jax too.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tpuframe.analysis import shardflow, source_lint
from tpuframe.parallel import quantwire, step as step_lib, zero1
from tpuframe.tune import db as tune_db


# ---------------------------------------------------------------------------
# Resolution precedence: env > tune_db > default.
# ---------------------------------------------------------------------------


def _wire_rec(program="train_lm_b8", family="wire_format_lm",
              gen="v5e", fmt="int8-block"):
    return {"program": program, "family": family, "fingerprint": "fp0",
            "topology": "v5e:2x2", "generation": gen,
            "config": {"wire_format": fmt, "batch": 8},
            "predicted": {"predicted_ms": 1.0, "bound": "hbm",
                          "fits": True, "vmem_bytes": 0,
                          "bytes_lower_bound": True}}


@pytest.fixture
def wire_db(tmp_path, monkeypatch):
    """A tuning DB with one swept int8-block winner, wired into the env
    the way the resolution chain reads it; the generation gate is left
    CLOSED (no gen env) — tests open it explicitly."""
    path = str(tmp_path / "tune_db.json")
    db = tune_db.TuningDB(path)
    db.add(_wire_rec())
    db.save()
    monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
    monkeypatch.delenv("TPUFRAME_WIRE_FORMAT", raising=False)
    monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
    return path


class TestResolution:
    def test_default_is_fp(self, wire_db):
        # DB exists but the generation gate is closed -> hard default.
        assert quantwire.resolve("train_lm_b8", "wire_format_lm") \
            == ("fp", "default")

    def test_db_elected_when_generation_matches(self, wire_db, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert quantwire.resolve("train_lm_b8", "wire_format_lm") \
            == ("int8-block", "tune_db")
        # family fallback: unknown program, known family
        assert quantwire.resolve("train_other_b4", "wire_format_lm") \
            == ("int8-block", "tune_db")

    def test_generation_gate(self, wire_db, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v4")
        assert quantwire.resolve("train_lm_b8", "wire_format_lm") \
            == ("fp", "default")

    def test_env_beats_db(self, wire_db, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv(quantwire.ENV_VAR, "fp")
        assert quantwire.resolve("train_lm_b8", "wire_format_lm") \
            == ("fp", "env")

    def test_env_invalid_raises(self, monkeypatch):
        # An explicit ask for something unknown is an error, never a
        # silent demotion — only DB rows demote silently.
        monkeypatch.setenv(quantwire.ENV_VAR, "int4-sparse")
        with pytest.raises(ValueError, match="int4-sparse"):
            quantwire.resolve()

    def test_stale_db_row_demotes_silently(self, tmp_path, monkeypatch):
        # A DB written by a future/older tpuframe may elect a format this
        # build doesn't know.  That must fall back to fp, not raise.
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add(_wire_rec(fmt="int3-exotic"))
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.delenv("TPUFRAME_WIRE_FORMAT", raising=False)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert quantwire.resolve("train_lm_b8", "wire_format_lm") \
            == ("fp", "default")

    def test_self_check_clean(self, monkeypatch):
        monkeypatch.delenv(quantwire.ENV_VAR, raising=False)
        assert quantwire.check() == []


# ---------------------------------------------------------------------------
# TF115: raw lax collectives in the wire-format seam.
# ---------------------------------------------------------------------------

_SEAM_PATH = "tpuframe/parallel/step.py"
_RAW_SRC = ("from jax import lax\n"
            "\n"
            "def _mean(x, ax):\n"
            "    return lax.psum(x, ax)\n")


class TestTF115:
    def test_flags_raw_collective_in_seam(self):
        found = [f for f in source_lint.lint_source(_RAW_SRC, _SEAM_PATH)
                 if f.rule == "TF115"]
        assert found and "wire" in found[0].message

    def test_other_modules_are_out_of_scope(self):
        findings = source_lint.lint_source(
            _RAW_SRC, "tpuframe/parallel/collectives.py")
        assert not [f for f in findings if f.rule == "TF115"]

    def test_pmean_is_the_fp_dispatch_target(self):
        # pmean IS what the resolved fp wire lowers to — flagging it
        # would make the seam unable to implement its own default.
        src = ("from jax import lax\n"
               "\n"
               "def _mean(x, ax):\n"
               "    return lax.pmean(x, ax)\n")
        findings = source_lint.lint_source(src, _SEAM_PATH)
        assert not [f for f in findings if f.rule == "TF115"]

    def test_suppression_on_the_call_line(self):
        src = ("from jax import lax\n"
               "\n"
               "def _norm(x, ax):\n"
               "    return lax.psum(x, ax)  # tf-lint: ok[TF115] scalar\n")
        findings = source_lint.lint_source(src, _SEAM_PATH)
        assert not [f for f in findings if f.rule == "TF115"]

    def test_real_seam_files_are_clean(self):
        import tpuframe.parallel as pp
        root = pp.__path__[0]
        findings = source_lint.lint_paths(
            [f"{root}/step.py", f"{root}/zero1.py"])
        assert not [f for f in findings if f.rule == "TF115"], findings


# ---------------------------------------------------------------------------
# shardflow: registration + the seeded positive.
# ---------------------------------------------------------------------------


class TestShardflowWire:
    def test_int8_block_registered(self):
        formats = shardflow.registered_wire_formats()
        assert formats.get("int8-block") == frozenset({"s8"})

    def test_seeded_positive_round_trip(self):
        # Clean registry: the seeded f32 all-reduce is exempted by no
        # narrow format, so the self-test passes...
        assert shardflow.seeded_wire_positive() == []
        # ...and a format registration claiming f32 is "narrow" must
        # trip it (a blinded wire_dtype detector fails loudly).
        shardflow.register_wire_format("f32-leak", {"s8", "f32"})
        try:
            assert shardflow.seeded_wire_positive() != []
        finally:
            del shardflow._WIRE_FORMATS["f32-leak"]
        assert shardflow.seeded_wire_positive() == []


# ---------------------------------------------------------------------------
# Derived budgets: the int8 strategies' wire bytes vs their fp twins.
# ---------------------------------------------------------------------------


def test_derived_budget_quantized_ratio():
    """The checked-in derived budgets must show the 4x per-leg drop: each
    quantized leg (s8 all-to-all for the reduce-scatter phase, s8
    all-gather back) carries 1/4 the bytes of the f32 gradient payload
    it replaced."""
    dp = shardflow.derived_for("dp")
    dpq = shardflow.derived_for("dp-int8")
    if dp is None or dpq is None:
        pytest.skip("derived budgets not emitted for this jax")
    a2a = dpq["above_floor"].get("all-to-all", 0)
    ag = dpq["above_floor"].get("all-gather", 0)
    assert a2a > 0 and a2a == ag, dpq["above_floor"]
    # dp's gradient all-reduce total (full census; the few non-gradient
    # scalar reduces add well under 2%).
    fp_bytes = dp["kinds"]["all-reduce"]["bytes"]
    assert abs(4 * a2a - fp_bytes) / fp_bytes < 0.02, (a2a, fp_bytes)

    dz = shardflow.derived_for("dp-zero1")
    dzq = shardflow.derived_for("dp-zero1-int8")
    if dz is None or dzq is None:
        pytest.skip("zero1 derived budgets not emitted for this jax")
    a2a_z = dzq["above_floor"].get("all-to-all", 0)
    ag_z = dzq["above_floor"].get("all-gather", 0)
    assert a2a_z > 0 and a2a_z == ag_z, dzq["above_floor"]
    rs_bytes = dz["kinds"]["reduce-scatter"]["bytes"]
    assert abs(4 * a2a_z - rs_bytes) / rs_bytes < 0.02, (a2a_z, rs_bytes)


# ---------------------------------------------------------------------------
# Golden loss: the int8 wire must track fp training, both update modes.
# ---------------------------------------------------------------------------


def _make_loss():
    def loss_fn(params, model_state, batch, rng_):
        x, y = batch
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        pred = h @ params["w2"] + params["b2"]
        return jnp.mean((pred - y) ** 2), (model_state, {})
    return loss_fn


def _init_params(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (32, 64)) * 0.1,
            "b1": jnp.zeros((64,)),
            "w2": jax.random.normal(k2, (64, 8)) * 0.1,
            "b2": jnp.zeros((8,))}


def _run(mesh, wire, weight_update="replicated", steps=25):
    import optax

    tx = optax.sgd(0.05, momentum=0.9)
    params = _init_params(jax.random.key(1))
    if weight_update == "zero1":
        state = zero1.make_state(params, tx, mesh)
    else:
        state = step_lib.TrainState.create(params, tx)
        state = step_lib.replicate_state(state, mesh)
    train = step_lib.make_train_step(_make_loss(), tx, mesh,
                                     weight_update=weight_update,
                                     wire_format=wire, donate=False)
    key = jax.random.key(2)
    w_true = jax.random.normal(jax.random.key(7), (32, 8))
    losses = []
    for _ in range(steps):
        key, k1 = jax.random.split(key)
        x = jax.random.normal(k1, (64, 32))
        y = jnp.sin(x @ w_true)
        state, metrics = train(state, (x, y))
        losses.append(float(metrics["loss"]))
    return np.array(losses)


@pytest.mark.parametrize("weight_update", ["replicated", "zero1"])
def test_golden_loss_int8_tracks_fp(mesh8, weight_update):
    """Loss-trajectory parity, the documented acceptance bound: per-step
    |loss_int8 - loss_fp| <= 2e-3 over the run (observed ~3e-5), and the
    int8 run itself trains."""
    l_fp = _run(mesh8, "fp", weight_update)
    l_q = _run(mesh8, "int8-block", weight_update)
    assert l_q[-1] < l_fp[0], "int8 run did not train"
    d = np.abs(l_q - l_fp)
    assert d.max() <= 2e-3, (weight_update, d.max())


def test_unknown_wire_format_rejected_at_build(mesh8):
    import optax

    with pytest.raises(ValueError, match="wire format"):
        step_lib.make_train_step(_make_loss(), optax.sgd(0.1), mesh8,
                                 wire_format="int5-wild")
