"""Whole-program TPU (Mosaic + XLA) AOT compile guards — no chip needed.

The slow half of ``tests/test_chip_compile.py``: a compile-only topology
(``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``)
runs the ENTIRE TPU compiler — Mosaic kernel codegen, XLA fusion/layout,
SPMD partitioning — on the CPU host.  These tests pin that the flagship
multi-device programs actually COMPILE for v5e:

  - the ResNet-50 DP train step partitioned over 4 devices (collectives
    present in the lowering);
  - a dp4 flash-attention step whose collectives fit the declared budget;
  - the flash kernel for a v4 target, and the remat sweep's CLI.

Everything compiles in this process (a second process that loads libtpu
while this one holds it aborts on libtpu's lockfile).  Only execution-time
behavior (numerics on the MXU, timing) still needs ``chip_smoke.py``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

pytestmark = [pytest.mark.slow,
              pytest.mark.usefixtures("no_persistent_compile_cache")]


def _replicated_shapes(tree, sharding):
    to_s = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=sharding)
    return jax.tree.map(
        lambda s: to_s(s) if hasattr(s, "shape") else s, tree,
        is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))


def test_resnet50_dp4_step_compiles_for_v5e(v5e_topology):
    import optax

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4),
                              devices=list(v5e_topology.devices))
    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, mesh_lib.batch_spec())
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
        jax.random.key(0))
    tx = optax.sgd(0.1, momentum=0.9)

    def loss_fn(params, model_state, b, rng):
        logits, mut = model.apply({"params": params, **model_state},
                                  b["image"], train=True,
                                  mutable=["batch_stats"])
        return losses.softmax_cross_entropy(logits, b["label"]), (
            dict(mut), {})

    state = _replicated_shapes(jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx,
            model_state={"batch_stats": v["batch_stats"]}), variables), repl)
    batch = {"image": jax.ShapeDtypeStruct((16, 224, 224, 3),
                                           jnp.bfloat16, sharding=dsh),
             "label": jax.ShapeDtypeStruct((16,), jnp.int32, sharding=dsh)}
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False)
    txt = jax.jit(step).lower(state, batch).compile().as_text()
    assert "all-reduce" in txt, "expected cross-replica collectives"


def _resnet18_all_reduce_counts(v5e_topology):
    """(all-reduce ops, tensors through them) of a ResNet-18 dp4 step
    compiled for v5e under the current TPUFRAME_FUSION_THRESHOLD."""
    import optax

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib
    from tpuframe.parallel import tuning

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4),
                              devices=list(v5e_topology.devices))
    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, mesh_lib.batch_spec())
    model = models.ResNet18(num_classes=10, cifar_stem=True,
                            dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 32, 32, 3), jnp.bfloat16)),
        jax.random.key(0))
    tx = optax.sgd(0.1)

    def loss_fn(params, model_state, b, rng):
        logits, mut = model.apply({"params": params, **model_state},
                                  b["x"], train=True,
                                  mutable=["batch_stats"])
        return losses.softmax_cross_entropy(logits, b["y"]), (dict(mut), {})

    state = _replicated_shapes(jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx,
            model_state={"batch_stats": v["batch_stats"]}), variables), repl)
    batch = {"x": jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.bfloat16,
                                       sharding=dsh),
             "y": jax.ShapeDtypeStruct((16,), jnp.int32, sharding=dsh)}
    step = step_lib.make_train_step(
        loss_fn, tx, mesh, donate=False,
        fusion_threshold=tuning.step_threshold())
    txt = jax.jit(step).lower(state, batch).compile().as_text()
    ops = tensors = 0
    for ln in txt.splitlines():
        m = re.match(r"%?[\w.-]+ = (.*?) all-reduce(-start)?\(", ln.strip())
        if not m:
            continue
        ops += 1
        tensors += len(re.findall(r"(?:bf16|f32)\[", m.group(1)))
    return ops, tensors


def test_fusion_threshold_on_v5e_combiner_owns_fusion(v5e_topology,
                                                      monkeypatch):
    """HOROVOD_FUSION_THRESHOLD on the REAL TPU compiler: the v5e
    combiner merges gradient reductions into ONE variadic all-reduce
    with or without the explicit program-level fusion buffers — i.e. on
    TPU the backend delivers Horovod's full fusion regardless of the
    knob (SURVEY.md §3b's L1 mapping, now compiler-verified).  The knob
    still changes the traced program: per-leaf mode ships many tensors
    through the single op, packed mode ships few buckets."""
    monkeypatch.setenv("TPUFRAME_FUSION_THRESHOLD", "0")
    ops_leaf, tensors_leaf = _resnet18_all_reduce_counts(v5e_topology)
    monkeypatch.setenv("TPUFRAME_FUSION_THRESHOLD", "67108864")
    ops_packed, tensors_packed = _resnet18_all_reduce_counts(v5e_topology)
    # Backend fusion: one combined all-reduce either way.
    assert ops_leaf == ops_packed == 1, (ops_leaf, ops_packed)
    # The program-level knob is still visible as the operand structure.
    assert tensors_leaf > tensors_packed >= 1, (tensors_leaf,
                                                tensors_packed)


def test_flash_attention_compiles_for_v4_target():
    """v4-generation Mosaic guard (PERF.md §12.1): the lse/delta rows must
    stay sublane-major — a lane-major layout lowers as tpu.dynamic_gather,
    which v4 rejects ('Sublane gather not supported').  This compile
    catches any regression without v4 hardware."""
    from tpuframe.ops.flash_attention import flash_mha_lse

    try:
        topo4 = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v4:2x2x1")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"cannot describe a v4 topology here: {e}")
    sh = NamedSharding(Mesh(np.array([topo4.devices[0]]), ("d",)), P())
    q = jax.ShapeDtypeStruct((2, 512, 4, 64), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        out, lse = flash_mha_lse(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum() + (lse * 0.5).sum()

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert "custom-call" in c.as_text()


def test_flash_attention_dp4_budget_audit_v5e(v5e_topology):
    """tpuframe.analysis over the REAL TPU compiler output: a dp4
    flash-attention train step is AOT-compiled for v5e and its
    collectives must fit the declared dp budget — the Mosaic kernel must
    not perturb the step's wire pattern, and the gradient all-reduce
    must be present and param-sized (the CI gate's deep half; the fast
    half audits CPU lowerings in tests/test_analysis.py)."""
    import optax

    from tpuframe.analysis import budgets, hlo_audit
    from tpuframe.ops.flash_attention import flash_mha
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4),
                              devices=list(v5e_topology.devices))
    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, mesh_lib.batch_spec())
    tx = optax.sgd(0.1)

    def loss_fn(params, model_state, b, rng):
        q = b["q"]
        o = flash_mha(q, q, q, causal=True, interpret=False)
        h = o.reshape(q.shape[0], q.shape[1], -1).astype(jnp.float32)
        return ((h @ params["w"]) ** 2).mean(), ({}, {})

    state = _replicated_shapes(jax.eval_shape(
        lambda: step_lib.TrainState.create(
            {"w": jnp.zeros((256, 1024), jnp.float32)}, tx)), repl)
    batch = {"q": jax.ShapeDtypeStruct((8, 512, 4, 64), jnp.bfloat16,
                                       sharding=dsh)}
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False)
    report, c = hlo_audit.audit_jitted(step, state, batch)
    assert "tpu_custom_call" in c.as_text()
    pb = 256 * 1024 * 4
    violations = budgets.check_budget(report, budgets.dp_budget(pb))
    assert not violations, violations
    ar = report.bytes_by_kind().get("all-reduce", 0)
    assert pb <= ar <= 2 * pb, (ar, pb, report.summary())


def test_remat_sweep_cli_smoke_v5e(tmp_path):
    """``python -m tpuframe.tune sweep --remat`` end to end on the real
    v5e compiler (2 policies, small batch to keep the compiles short):
    both policies compile, the report ranks by cost_analysis bytes, the
    winner lands in the tuning DB with a ``remat_policy`` config, and
    the mechanism PERF.md §16 documents holds — per_block CUTS temp
    (live-activation) memory vs none.  Bytes-accessed is recorded but
    deliberately not ordered here: on this conv net recompute
    re-materializes through HBM, so remat is a capacity lever, not a
    bandwidth one (§16's honest finding)."""
    import json

    from tpuframe.tune import __main__ as tune_cli
    from tpuframe.tune import db as tune_db

    db = tmp_path / "tune_db.json"
    report = tmp_path / "remat_report.json"
    rc = tune_cli.main(
        ["sweep", "--remat", "--topology", "v5e:2x2", "--remat-batch", "64",
         "--remat-policies", "none", "per_block",
         "--db", str(db), "--report", str(report)])
    assert not rc

    rep = json.loads(report.read_text())
    assert rep["remat"]["compile_errors"] == []
    rows = {r["policy"]: r for r in rep["remat"]["rows"]}
    assert set(rows) == {"none", "per_block"}, rep["remat"]["rows"]
    for r in rows.values():
        assert r["gb"] > 0 and r["temp_gb"] > 0
        assert r["drop_vs_none_pct"] is not None
    # The capacity mechanism: per-block remat halves-ish live residency.
    assert rows["per_block"]["temp_gb"] < rows["none"]["temp_gb"]
    assert rep["winner"]["policy"] in rows

    tdb = tune_db.TuningDB.open(str(db))
    recs = tdb.records(family="remat_resnet50", generation="v5e")
    assert {r.config["remat_policy"] for r in recs} == {"none",
                                                        "per_block"}
    best = tdb.best(family="remat_resnet50", generation="v5e")
    assert best.config["remat_policy"] == rep["winner"]["policy"]
