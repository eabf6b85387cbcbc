"""The hierarchical-collective seam end to end: resolution precedence
(env > tuning DB > default, stale rows demote silently) for the hier
mode, the TF124 slice-axis seam lint,
fabric attribution of the compiled two-level lowering (in-slice groups
on ICI, cross-slice groups on DCN), byte-exact derived-budget pins of
the 1/n_inner DCN law, golden-loss parity of hier vs flat for both
weight-update modes, the compose-rejection matrix, the MegaScale
host-transfer DCN parser, and the compare differ's DCN regression rule.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tpuframe.analysis import collective_graph as cg
from tpuframe.analysis import hlo_audit, shardflow, source_lint
from tpuframe.parallel import hier, step as step_lib, zero1
from tpuframe.tune import db as tune_db


@pytest.fixture(scope="module")
def smesh():
    """4-way data x 2-slice mesh on the 8 virtual CPU devices — the
    smallest world where the two-level lowering has both fabrics."""
    from tpuframe.parallel import mesh as mesh_lib

    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4, slices=2))


# ---------------------------------------------------------------------------
# Resolution precedence: env > tune_db > default.
# ---------------------------------------------------------------------------


def _hier_rec(program="train_lm_b8", gen="v5e", mode="hier"):
    return {"program": program, "family": "hier_collectives",
            "fingerprint": "fp0", "topology": "v5e:2x2",
            "generation": gen,
            "config": {"hier": mode, "batch": 8, "weight_update": "replicated",
                       "slices": 2},
            "predicted": {"predicted_ms": 1.0, "bound": "hbm",
                          "fits": True, "vmem_bytes": 0,
                          "bytes_lower_bound": True}}


@pytest.fixture
def hier_db(tmp_path, monkeypatch):
    """A tuning DB with one swept hier winner, wired into the
    env the way the resolution chain reads it; the generation gate is
    left CLOSED (no gen env) — tests open it explicitly."""
    path = str(tmp_path / "tune_db.json")
    db = tune_db.TuningDB(path)
    db.add(_hier_rec())
    db.save()
    monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
    monkeypatch.delenv("TPUFRAME_HIER", raising=False)
    monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
    return path


class TestResolution:
    def test_default_is_flat(self, hier_db):
        # DB exists but the generation gate is closed -> hard default.
        assert hier.resolve("train_lm_b8", "hier_collectives") \
            == ("flat", "default")

    def test_db_elected_when_generation_matches(self, hier_db,
                                                monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert hier.resolve("train_lm_b8", "hier_collectives") \
            == ("hier", "tune_db")
        # family fallback: unknown program, known family
        assert hier.resolve("train_other_b4", "hier_collectives") \
            == ("hier", "tune_db")

    def test_generation_gate(self, hier_db, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v4")
        assert hier.resolve("train_lm_b8", "hier_collectives") \
            == ("flat", "default")

    def test_env_beats_db(self, hier_db, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv(hier.ENV_VAR, "flat")
        assert hier.resolve("train_lm_b8", "hier_collectives") \
            == ("flat", "env")

    def test_env_invalid_raises(self, monkeypatch):
        monkeypatch.setenv(hier.ENV_VAR, "diagonal")
        with pytest.raises(ValueError, match="diagonal"):
            hier.resolve()

    def test_stale_db_row_demotes_silently(self, tmp_path, monkeypatch):
        # A DB written by a future/older tpuframe may elect a mode this
        # build doesn't know.  That must fall back to flat, not raise.
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add(_hier_rec(mode="diagonal"))
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.delenv("TPUFRAME_HIER", raising=False)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert hier.resolve("train_lm_b8", "hier_collectives") \
            == ("flat", "default")

    def test_self_check_clean(self, monkeypatch):
        monkeypatch.delenv(hier.ENV_VAR, raising=False)
        assert hier.check() == []


# ---------------------------------------------------------------------------
# TF124: collectives naming the slice (DCN) axis outside the seam.
# ---------------------------------------------------------------------------

_SEAM_PATH = "tpuframe/parallel/hier.py"
_RAW_SRC = ("from jax import lax\n"
            "\n"
            "def _mean(x):\n"
            "    return lax.pmean(x, ('slice', 'data'))\n")


class TestTF124:
    def test_flags_slice_collective_outside_seam(self):
        found = [f for f in source_lint.lint_source(
            _RAW_SRC, "tpuframe/parallel/zero1.py")
            if f.rule == "TF124"]
        assert found and "slice" in found[0].message

    def test_seam_module_is_exempt(self):
        findings = source_lint.lint_source(_RAW_SRC, _SEAM_PATH)
        assert not [f for f in findings if f.rule == "TF124"]

    def test_computed_axes_are_out_of_scope(self):
        # The seam's callers hand computed axis tuples down — only the
        # bare "slice" literal marks hand-routed DCN traffic.
        src = ("from jax import lax\n"
               "\n"
               "def _mean(x, axes):\n"
               "    return lax.pmean(x, axes)\n")
        findings = source_lint.lint_source(
            src, "tpuframe/parallel/step.py")
        assert not [f for f in findings if f.rule == "TF124"]

    def test_suppression_on_the_call_line(self):
        src = ("from jax import lax\n"
               "\n"
               "def _mean(x):\n"
               "    return lax.pmean(x, 'slice')"
               "  # tf-lint: ok[TF124] probe\n")
        findings = source_lint.lint_source(
            src, "tpuframe/parallel/step.py")
        assert not [f for f in findings if f.rule == "TF124"]

    def test_real_caller_files_are_clean(self):
        import tpuframe.parallel as pp
        root = pp.__path__[0]
        findings = source_lint.lint_paths(
            [f"{root}/step.py", f"{root}/zero1.py",
             f"{root}/collectives.py"])
        assert not [f for f in findings if f.rule == "TF124"], findings


# ---------------------------------------------------------------------------
# Derived budgets: the 1/n_inner DCN law, pinned byte-exact.
# ---------------------------------------------------------------------------


def test_derived_budget_hier_dcn_law():
    """The checked-in derived budgets must show the two-level shape
    exactly: the in-slice reduce-scatter and all-gather carry the full
    gradient payload and the cross-slice all-reduce carries payload /
    n_inner (n_inner = 4 on the 2-slice 8-device mesh)."""
    flat = shardflow.derived_for("spec:dp=*;slices=2")
    h = shardflow.derived_for("spec:dp=*;slices=2+hier")
    if flat is None or h is None:
        pytest.skip("derived budgets not emitted for this jax")
    rs = h["above_floor"].get("reduce-scatter", 0)
    ag = h["above_floor"].get("all-gather", 0)
    ar = h["above_floor"].get("all-reduce", 0)
    assert rs > 0 and rs == ag, h["above_floor"]
    assert ar * 4 == rs, (ar, rs)  # the 1/n_inner law, byte-exact
    # ...and the cross-slice leg is under half the flat program's
    # whole gradient all-reduce (the DCN-ratio acceptance bound).
    flat_ar = flat["kinds"]["all-reduce"]["bytes"]
    assert 2 * ar <= flat_ar, (ar, flat_ar)


def test_derived_budget_zero1_hier_dcn_law():
    z = shardflow.derived_for("spec:dp=*;slices=2+zero1")
    zh = shardflow.derived_for("spec:dp=*;slices=2+zero1+hier")
    if z is None or zh is None:
        pytest.skip("derived budgets not emitted for this jax")
    # The two-stage scatter/gather pay the full padded payload in-slice
    # plus the 1/n_inner chunk across slices: flat * (1 + 1/4) per kind.
    for kind in ("reduce-scatter", "all-gather"):
        flat_b = z["above_floor"].get(kind, 0)
        assert flat_b > 0, z["above_floor"]
        assert zh["above_floor"].get(kind, 0) * 4 == flat_b * 5, (kind, zh)


# ---------------------------------------------------------------------------
# Compiled fabric attribution: two-level groups land on the right wires.
# ---------------------------------------------------------------------------


def _make_loss():
    def loss_fn(params, model_state, batch, rng_):
        x, y = batch
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        pred = h @ params["w2"] + params["b2"]
        return jnp.mean((pred - y) ** 2), (model_state, {})
    return loss_fn


def _init_params(key):
    # w1 clears hier.MIN_TWO_LEVEL_ELEMS, so it takes the two-level
    # lowering; the biases keep the flat cross-slice mean by design.
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (64, 128)) * 0.1,
            "b1": jnp.zeros((128,)),
            "w2": jax.random.normal(k2, (128, 8)) * 0.1,
            "b2": jnp.zeros((8,))}


def _lower_hlo(mesh, hier_mode, weight_update="replicated"):
    import optax

    tx = optax.sgd(0.05)
    params = _init_params(jax.random.key(1))
    if weight_update == "zero1":
        state = zero1.make_state(params, tx, mesh)
    else:
        state = step_lib.TrainState.create(params, tx)
        state = step_lib.replicate_state(state, mesh)
    train = step_lib.make_train_step(_make_loss(), tx, mesh,
                                     weight_update=weight_update,
                                     hier=hier_mode,
                                     donate=False)
    x = jnp.zeros((64, 64))
    y = jnp.zeros((64, 8))
    return train.lower(state, (x, y)).compile().as_text()


def _split(hlo, floor=1024):
    coll = hlo_audit.parse_collectives(hlo)
    return shardflow.comm_split(cg.parse_graph(hlo), coll.filter(floor),
                                mesh_shape={"slice": 2, "data": 4},
                                n_devices=8)


class TestCompiledFabricSplit:
    def test_flat_crosses_slices_everywhere(self, smesh):
        split = _split(_lower_hlo(smesh, "flat"))
        assert split["dcn_bytes"] > 0
        assert split["ici_bytes"] == 0, split["ici"]

    def test_hier_moves_the_bulk_onto_ici(self, smesh):
        flat = _split(_lower_hlo(smesh, "flat"))
        h = _split(_lower_hlo(smesh, "hier"))
        assert h["ici_bytes"] > 0, h
        assert 2 * h["dcn_bytes"] <= flat["dcn_bytes"], (h, flat)

    def test_two_level_replica_groups_materialize(self, smesh):
        # slice-major device order: in-slice groups are the contiguous
        # quads, cross-slice groups the stride-4 pairs.
        hlo = _lower_hlo(smesh, "hier")
        assert re.search(r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}",
                         hlo), "in-slice (ICI) groups missing"
        assert re.search(r"replica_groups=\{\{0,4\},\{1,5\},\{2,6\},"
                         r"\{3,7\}\}", hlo), \
            "cross-slice (DCN) groups missing"


# ---------------------------------------------------------------------------
# Golden loss: the two-level mean must track the flat mean exactly.
# ---------------------------------------------------------------------------


def _run(mesh, hier_mode, weight_update="replicated", steps=25):
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
                                     hier=hier_mode,
                                     donate=False)
    key = jax.random.key(2)
    w_true = jax.random.normal(jax.random.key(7), (64, 8))
    losses = []
    for _ in range(steps):
        key, k1 = jax.random.split(key)
        x = jax.random.normal(k1, (64, 64))
        y = jnp.sin(x @ w_true)
        state, metrics = train(state, (x, y))
        losses.append(float(metrics["loss"]))
    return np.array(losses)


@pytest.mark.parametrize("weight_update", ["replicated", "zero1"])
def test_golden_loss_hier_matches_flat(smesh, weight_update):
    """The fp two-level mean is the flat mean re-associated — per-step
    loss parity to float-reassociation noise (observed ~1e-7)."""
    l_flat = _run(smesh, "flat", weight_update=weight_update)
    l_hier = _run(smesh, "hier", weight_update=weight_update)
    assert l_hier[-1] < l_flat[0], "hier run did not train"
    d = np.abs(l_hier - l_flat)
    assert d.max() <= 1e-4, (weight_update, d.max())


# ---------------------------------------------------------------------------
# Compose rejections: the matrix is an API contract, not advice.
# ---------------------------------------------------------------------------


class TestComposeRejections:
    def test_hier_needs_shard_map(self, smesh):
        import optax

        with pytest.raises(ValueError, match="shard_map"):
            step_lib.make_train_step(_make_loss(), optax.sgd(0.1), smesh,
                                     mode="jit", hier="hier")

    def test_hier_rejects_adasum(self, smesh):
        import optax

        with pytest.raises(ValueError, match="adasum"):
            step_lib.make_train_step(_make_loss(), optax.sgd(0.1), smesh,
                                     grad_reduce="adasum", hier="hier")


# ---------------------------------------------------------------------------
# MegaScale host-transfer parser: the DCN bytes HLO hides from the
# collective census on the compile-only multi-slice backend.
# ---------------------------------------------------------------------------

_MS_ATTRS = ('frontend_attributes={_xla_host_transfer_handler_name='
             '"xla_megascale_runtime",_xla_host_transfer_rendezvous='
             '"all-reduce.73_3"}')
_MS_SEND = ('  %send.1 = (f32[1025,8,128]{2,1,0}, u32[], token[]) '
            'send(%x, %tok), channel_id=5, is_host_transfer=true, '
            + _MS_ATTRS)
_MS_SEND_S8 = ('  %send.2 = (s8[4096]{0}, u32[], token[]) '
               'send(%q, %tok), channel_id=6, is_host_transfer=true, '
               + _MS_ATTRS)


class TestMegascaleSplit:
    def test_counts_payload_bytes_by_kind(self):
        out = shardflow.megascale_split("\n".join([_MS_SEND,
                                                   _MS_SEND_S8]))
        assert out == {"all-reduce": 1025 * 8 * 128 * 4 + 4096}

    def test_ignores_non_megascale_transfers(self):
        plain = ('  %send.3 = (f32[64]{0}, u32[], token[]) '
                 'send(%x, %tok), channel_id=7, is_host_transfer=true, '
                 'frontend_attributes={_xla_host_transfer_rendezvous='
                 '"infeed"}')
        assert shardflow.megascale_split(plain) == {}

    def test_ignores_recv_and_send_done(self):
        others = ('  %recv.1 = (f32[64]{0}, u32[], token[]) '
                  'recv(%tok), is_host_transfer=true, ' + _MS_ATTRS
                  + '\n  %send-done.1 = token[] send-done(%send.1), '
                    'is_host_transfer=true, ' + _MS_ATTRS)
        assert shardflow.megascale_split(others) == {}

    def test_empty_on_cpu_hlo(self, smesh):
        # Folding megascale bytes into the DCN column must be a no-op
        # where XLA emits real collectives.
        assert shardflow.megascale_split(_lower_hlo(smesh, "hier")) == {}


# ---------------------------------------------------------------------------
# The compare differ's DCN rule: growth flags, the crush direction never.
# ---------------------------------------------------------------------------


def _report(dcn_bytes=None):
    strat = {"name": "dp", "status": "ok", "violations": [],
             "derived": {"ignore_below": 1024, "kinds": {},
                         "above_floor": {}, "total_bytes": 0},
             "detectors": {}}
    if dcn_bytes is not None:
        strat["comm_split"] = {"slices": 2, "dcn_bytes": int(dcn_bytes),
                               "ici_bytes": 0}
    return {"strategies": [strat]}


class TestCompareDcnRule:
    def test_growth_is_a_regression(self):
        rc, lines = shardflow.compare_reports(_report(100000),
                                              _report(120001))
        assert rc == 1 and any("DCN bytes" in ln for ln in lines)

    def test_newly_crossing_slices_is_a_regression(self):
        rc, lines = shardflow.compare_reports(_report(0), _report(4096))
        assert rc == 1
        assert any("newly cross slices" in ln for ln in lines)

    def test_crush_direction_is_never_flagged(self):
        rc, lines = shardflow.compare_reports(_report(296196),
                                              _report(73728))
        assert rc == 0, lines

    def test_section_gated_on_both_reports(self):
        rc, _lines = shardflow.compare_reports(_report(None),
                                               _report(4096))
        assert rc == 0
