"""Offline capacity audit: AOT-compile every beyond-DP flagship config at
its REAL shape against compile-only v5e devices.

Motivation (PERF.md §9): the 32k ring-attention step OOM'd at real scale
while every CI test passed at toy shapes.  This audit closes that class
for the remaining parallelism strategies — each entry compiles the full
production-sized step on an 8-device v5e topology and records bytes /
temp memory / collectives, or an honest compile_error row.

  lm_long_exact   — the lm_long config verbatim: dp1 x sp8, b=8,
                    seq 32768, ring attention + fused xent.
  lm_pp_realistic — ScanBlockLM 124M-class over pipe=4 x data=2,
                    b=8 x seq 2048 (GPipe microbatching).
  lm_moe_realistic— MoE TransformerLM, 8 experts over ep=4 x data=2,
                    b=8 x seq 2048.

Usage: python perf/exp_capacity_audit.py [name|all]
Appends JSON lines to perf/results/offline_ab.jsonl.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import (ensure_cpu_backend, hold_aot_lock,  # noqa: E402
                     to_shape_structs)

ensure_cpu_backend()
hold_aot_lock()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results",
                   "offline_ab.jsonl")

# 8-device compile-only topology: "v5e:2x4" (16 GB HBM) by default;
# TOPO=v4:2x2x2 re-audits every entry against the v4 family (32 GB HBM,
# the BASELINE.json:5 north-star hardware) — VERDICT r4 #5.
TOPO = os.environ.get("TOPO", "v5e:2x4")


def log(m):
    print(f"[capacity] {m}", file=sys.stderr, flush=True)


def _tag(base):
    from _common import topo_tag_suffix

    return base + topo_tag_suffix(TOPO, "v5e:2x4")


def record(row):
    row["source"] = f"offline AOT {TOPO} topology compile"
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)


def _summarize(c, tag, extra):
    txt = c.as_text()
    ca = c.cost_analysis() or {}
    ma = c.memory_analysis()
    # Residency = temp + arguments (+ undonated outputs): temp alone
    # understates a config at the capacity edge (review catch — the
    # replicated params/moments are argument memory, ~GBs at dp1).
    arg = ma.argument_size_in_bytes
    outb = ma.output_size_in_bytes
    alias = getattr(ma, "alias_size_in_bytes", 0)
    row = {"tag": tag,
           "bytes": ca.get("bytes accessed", 0.0),
           "gb_per_dev": round(ca.get("bytes accessed", 0.0) / 1e9, 2),
           "flops_per_dev": ca.get("flops", 0.0),
           "temp_gb_per_dev": round(ma.temp_size_in_bytes / 1e9, 2),
           "arg_gb_per_dev": round(arg / 1e9, 2),
           "out_gb_per_dev": round(outb / 1e9, 2),
           "alias_gb_per_dev": round(alias / 1e9, 2),
           "resident_gb_per_dev": round(
               (ma.temp_size_in_bytes + arg + outb - alias) / 1e9, 2),
           "collective_permutes": (txt.count("collective-permute(")
                                   + txt.count("collective-permute-start(")),
           "all_to_alls": txt.count(" all-to-all("),
           "all_reduces": (txt.count(" all-reduce(")
                           + txt.count(" all-reduce-start("))}
    row.update(extra)
    return row


def _lm_long(tag, data, sp, batch, seq_mode="ring", attn_impl="xla"):
    """Shared 32k sequence-parallel builder (dp x sp, ring or ulysses)."""
    from tpuframe import models
    from tpuframe.ops import fused_xent as fx
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    topo = topologies.get_topology_desc(TOPO, platform="tpu")
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=data, seq=sp),
                              devices=list(topo.devices))
    SEQ = 32768
    model = models.get_model(
        "transformer-lm", hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, vocab_size=32000, max_seq=SEQ,
        seq_mode=seq_mode, attn_impl=attn_impl, remat=True,
        dtype="bfloat16")
    repl = NamedSharding(mesh, P())
    part = P(mesh_lib.BATCH_AXES, "seq")
    ids = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32,
                               sharding=NamedSharding(mesh, part))
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, SEQ), jnp.int32)),
        jax.random.key(0))
    tx = optax.adamw(3e-4)

    def loss_fn(params, model_state, b, rng):
        hidden = model.apply({"params": params}, b["input_ids"], train=True,
                             rngs={"dropout": rng}, hidden_only=True)
        loss = jnp.mean(fx.fused_softmax_xent(
            hidden, params["lm_head"]["kernel"], b["labels"]))
        return loss, ({}, {})

    state = to_shape_structs(jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx), variables),
        repl)
    step = step_lib.make_train_step(
        loss_fn, tx, mesh, donate=True, batch_partition=part,
        reduce_axes=(*mesh_lib.BATCH_AXES, "seq"))
    log(f"compiling {tag} (dp{data} x sp{sp}, b={batch}, 32k)...")
    # step is already jitted WITH donation; an outer jax.jit would wrap
    # it in a donation-less jit and erase the aliasing from the audit.
    c = step.lower(state, {"input_ids": ids, "labels": ids}).compile()
    record(_summarize(c, _tag(tag), {"devices": 8, "seq": SEQ, "batch": batch}))


def lm_long_exact():
    """lm_long verbatim: dp1 x sp8, global batch 8, seq 32768."""
    _lm_long("lm_long_exact_dp1sp8", 1, 8, 8)


def lm_32k_dp2sp4():
    """The PERF.md section-9 headline variant: dp2 x sp4, b=2, 32k."""
    _lm_long("lm_32k_sp_ring_dp2sp4", 2, 4, 2)


def lm_32k_ring_pallas():
    """Ring attention with FLASH stages (round-5: flash_mha_lse + the
    logsumexp stage merge) at the same dp2 x sp4 32k shape — the direct
    A/B against both the xla-stage ring (round-4 row: the >=2x byte
    penalty) and Ulysses+flash.  Ring is the documented fallback when
    heads don't divide sp, so its stages must not be byte-penalized."""
    _lm_long("lm_32k_sp_ring_pallas_dp2sp4", 2, 4, 2,
             seq_mode="ring", attn_impl="pallas")


def lm_long_exact_pallas():
    """lm_long verbatim (dp1 x sp8, b=8, 32k) with flash ring stages."""
    _lm_long("lm_long_exact_pallas_dp1sp8", 1, 8, 8,
             seq_mode="ring", attn_impl="pallas")


def lm_32k_ulysses():
    """Ulysses (all-to-all head-resharding) at the same 32k shape —
    the other first-class SP mode, at real scale.  The inner attention
    MUST be the flash kernel: after resharding, each device holds the
    FULL 32k sequence on heads/sp heads, and XLA attention's S^2 scores
    OOM (20.3 GB vs 15.75 — the audit's xla-inner row records exactly
    that).  Pairing rule documented in PERF.md section 9."""
    _lm_long("lm_32k_sp_ulysses_pallas_dp2sp4", 2, 4, 2,
             seq_mode="ulysses", attn_impl="pallas")


def lm_tp_realistic():
    """Megatron-style tensor parallel at real shape: tp4 x dp2, 124M LM,
    b=8 s=2048, sharded state via the fsdp/tp rule tree."""
    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import fsdp as fsdp_lib
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib
    from tpuframe.parallel import tp as tp_lib

    topo = topologies.get_topology_desc(TOPO, platform="tpu")
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=2, model=4),
                              devices=list(topo.devices))
    model = models.get_model(
        "transformer-lm", hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, vocab_size=32000, max_seq=2048,
        dtype="bfloat16", remat=True)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 2048), jnp.int32)),
        jax.random.key(0))
    tx = optax.adamw(3e-4)

    def loss_fn(params, model_state, b, rng):
        logits = model.apply({"params": params}, b["input_ids"], train=True,
                             rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx), variables)
    shardings = fsdp_lib.state_shardings(
        state, mesh, tp_rules=tp_lib.rules_for_model("transformer-lm"))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
        if hasattr(s, "shape") else s, state, shardings,
        is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))
    ids = jax.ShapeDtypeStruct(
        (8, 2048), jnp.int32,
        sharding=NamedSharding(mesh, mesh_lib.batch_spec()))
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=True,
                                    state_shardings=shardings)
    log("compiling TP LM (tp4 x data2, b=8 s=2048)...")
    c = step.lower(state, {"input_ids": ids, "labels": ids}).compile()
    record(_summarize(c, _tag("lm_tp_tp4data2"), {
        "devices": 8, "seq": 2048, "batch": 8}))


def lm_pp_realistic():
    """ScanBlockLM over pipe=4 x data=2 at 124M-class size, b=8 s=2048."""
    from tpuframe.models.transformer_lm import LMConfig, ScanBlockLM
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import pp_lm
    from tpuframe.parallel import step as step_lib

    topo = topologies.get_topology_desc(TOPO, platform="tpu")
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=2, pipe=4),
                              devices=list(topo.devices))
    cfg = LMConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                   num_heads=12, intermediate_size=3072, max_seq=2048,
                   dtype="bfloat16", remat=True, dropout=0.0)
    model = ScanBlockLM(cfg)
    tx = optax.adamw(3e-4)
    abstract = jax.eval_shape(
        lambda k: step_lib.TrainState.create(
            model.init(k, jnp.zeros((1, 2048), jnp.int32))["params"], tx),
        jax.random.key(0))
    specs = pp_lm.state_partition(abstract)
    state = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp))
        if hasattr(s, "shape") else s, abstract, specs,
        is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))
    factory, _, _ = pp_lm.make_pp_lm_step(model, tx, mesh, n_micro=4)
    step = factory(abstract)
    ids = jax.ShapeDtypeStruct(
        (8, 2048), jnp.int32,
        sharding=NamedSharding(mesh, P(mesh_lib.BATCH_AXES)))
    log("compiling pp LM (pipe4 x data2, 124M-class, b=8 s=2048)...")
    c = step.lower(state, {"input_ids": ids, "labels": ids}).compile()
    record(_summarize(c, _tag("lm_pp_pipe4data2"), {
        "devices": 8, "seq": 2048, "batch": 8}))


def lm_moe_realistic():
    """MoE TransformerLM: 8 experts over ep=4 x data=2, b=8 s=2048."""
    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import fsdp as fsdp_lib
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib
    from tpuframe.parallel import tp as tp_lib

    topo = topologies.get_topology_desc(TOPO, platform="tpu")
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=2, expert=4),
                              devices=list(topo.devices))
    model = models.get_model(
        "transformer-lm", hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, vocab_size=32000, max_seq=2048,
        dtype="bfloat16", remat=True, moe_experts=8, moe_k=2, moe_every=2)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 2048), jnp.int32)),
        jax.random.key(0))
    tx = optax.adamw(3e-4)

    def loss_fn(params, model_state, b, rng):
        logits, sown = model.apply({"params": params}, b["input_ids"],
                                   train=True, rngs={"dropout": rng},
                                   mutable=["aux_loss"])
        loss = losses.softmax_cross_entropy(logits, b["labels"])
        leaves = jax.tree.leaves(sown)
        aux = sum(leaves) / max(len(leaves), 1)
        return loss + 0.01 * aux, ({}, {})

    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx), variables)
    shardings = fsdp_lib.state_shardings(
        state, mesh, tp_rules=tp_lib.rules_for_model("transformer-lm"))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
        if hasattr(s, "shape") else s, state, shardings,
        is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))
    ids = jax.ShapeDtypeStruct(
        (8, 2048), jnp.int32,
        sharding=NamedSharding(mesh, mesh_lib.batch_spec()))
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=True,
                                    state_shardings=shardings)
    log("compiling MoE LM (ep4 x data2, 8 experts, b=8 s=2048)...")
    c = step.lower(state, {"input_ids": ids, "labels": ids}).compile()
    record(_summarize(c, _tag("lm_moe_ep4data2"), {
        "devices": 8, "seq": 2048, "batch": 8, "experts": 8}))


ENTRIES = {
    "lm_long_exact": (lm_long_exact, {
        "tag": "lm_long_exact_dp1sp8", "devices": 8, "seq": 32768,
        "batch": 8}),
    "lm_32k_dp2sp4": (lm_32k_dp2sp4, {
        "tag": "lm_32k_sp_ring_dp2sp4", "devices": 8, "seq": 32768,
        "batch": 2}),
    "lm_32k_ring_pallas": (lm_32k_ring_pallas, {
        "tag": "lm_32k_sp_ring_pallas_dp2sp4", "devices": 8, "seq": 32768,
        "batch": 2}),
    "lm_long_exact_pallas": (lm_long_exact_pallas, {
        "tag": "lm_long_exact_pallas_dp1sp8", "devices": 8, "seq": 32768,
        "batch": 8}),
    "lm_32k_ulysses": (lm_32k_ulysses, {
        "tag": "lm_32k_sp_ulysses_pallas_dp2sp4", "devices": 8,
        "seq": 32768, "batch": 2}),
    "lm_tp_realistic": (lm_tp_realistic, {
        "tag": "lm_tp_tp4data2", "devices": 8, "seq": 2048, "batch": 8}),
    "lm_pp_realistic": (lm_pp_realistic, {
        "tag": "lm_pp_pipe4data2", "devices": 8, "seq": 2048, "batch": 8}),
    "lm_moe_realistic": (lm_moe_realistic, {
        "tag": "lm_moe_ep4data2", "devices": 8, "seq": 2048, "batch": 8,
        "experts": 8}),
}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    targets = ENTRIES.values() if which == "all" else [ENTRIES[which]]
    for fn, meta in targets:
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            record({**meta, "compile_error": str(e)[:400]})


if __name__ == "__main__":
    main()
