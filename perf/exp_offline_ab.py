"""Offline AOT A/Bs on the compile-only v5e topology (PERF.md §7).

Extends `exp_hlo_offline.py`'s discovery to the transformer workloads and
the multi-chip DP program — compiler-measured evidence (bytes accessed,
flops, temp memory, collective payloads) with no chip in the loop:

  lm_xent  — TransformerLM 124M b=8 s=2048: dense head+loss vs the
             chunked fused softmax-xent (tpuframe/ops/fused_xent.py).
             The fused op's claim is that the [B,S,V] logits never land
             in HBM; `bytes accessed` is the direct check.
  lm_8k    — b=2 s=8192: XLA full attention vs the pallas flash kernel.
             On-chip the XLA variant FAILS TO COMPILE (S^2 scores at
             seq 8k, BASELINE.md round 3); AOT memory_analysis shows the
             footprint both ways without needing 16 GB of real HBM.
  dp32     — ResNet-50 DP train step over 32 compile-only v5e devices
             (topology 4x8): the all-reduce payloads of the ACTUAL TPU
             lowering, cross-checking tests/test_scaling32.py's
             CPU-mesh HLO and the scaling projection's traffic input.
  bert_b256— BERT-base classification step at b=256 s=128: the
             queue-4 on-chip A/B's byte/temp picture, offline.
  remat    — the donated ResNet-50 b=512 train step under tpuframe.mem
             remat policies (REMAT_POLICIES=comma,list overrides the
             default none,dots,per_block set).  Rows carry a ``policy``
             column; the _ab_rows key is (tag, policy), so every policy
             row survives next to the ``none`` baseline.

Usage:  python perf/exp_offline_ab.py [lm_xent|lm_8k|dp32|bert_b256|remat|all]
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

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results",
                   "offline_ab.jsonl")


def log(m):
    print(f"[offline-ab] {m}", file=sys.stderr, flush=True)


def record(row):
    row["source"] = "offline AOT v5e topology compile"
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)


def _topo_mesh(shape="v5e:2x2", n=1, axes=("data",)):
    topo = topologies.get_topology_desc(shape, platform="tpu")
    devs = np.array(topo.devices[:n]).reshape([n] if len(axes) == 1 else None)
    return Mesh(devs, axes)


def _analyze(compiled, tag, extra=None):
    ca = compiled.cost_analysis() or {}
    row = {"tag": tag, "flops": ca.get("flops", 0.0),
           "bytes": ca.get("bytes accessed", 0.0),
           "gb": round(ca.get("bytes accessed", 0.0) / 1e9, 2)}
    try:
        ma = compiled.memory_analysis()
        row["temp_gb"] = round(ma.temp_size_in_bytes / 1e9, 2)
        row["arg_gb"] = round(ma.argument_size_in_bytes / 1e9, 2)
    except Exception as e:  # noqa: BLE001
        row["memory_analysis_error"] = str(e)[:120]
    if extra:
        row.update(extra)
    return row


def _lm_step(seq, batch_size, attn_impl, fused, repl):
    from tpuframe.models import losses
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.parallel import step as step_lib

    cfg = LMConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                   num_heads=12, intermediate_size=3072, max_seq=seq,
                   dtype="bfloat16", attn_impl=attn_impl, remat=True)
    model = TransformerLM(cfg)
    ids = jax.ShapeDtypeStruct((batch_size, seq), jnp.int32, sharding=repl)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, seq), jnp.int32)),
        jax.random.key(0))
    tx = optax.adamw(1e-4)

    if fused:
        from tpuframe.ops import fused_xent as fx

        def loss_fn(params, model_state, b, rng):
            hidden = model.apply({"params": params}, b["input_ids"],
                                 train=True, rngs={"dropout": rng},
                                 hidden_only=True)
            w = params["lm_head"]["kernel"]
            loss = jnp.mean(fx.fused_softmax_xent(hidden, w, b["labels"]))
            return loss, ({}, {})
    else:
        def loss_fn(params, model_state, b, rng):
            logits = model.apply({"params": params}, b["input_ids"],
                                 train=True, rngs={"dropout": rng})
            return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx), variables)
    state = to_shape_structs(state, repl)
    step = step_lib.make_train_step(loss_fn, tx, None, donate=False)
    batch = {"input_ids": ids, "labels": ids}
    return step, state, batch


def lm_xent():
    mesh = _topo_mesh(n=1)
    repl = NamedSharding(mesh, P())
    # Third variant is the PERF.md §8 headline row: flash attention +
    # fused head — the byte-minimal LM step.
    for attn, fused, tag in (("xla", False, "lm_2k_dense_xent"),
                             ("xla", True, "lm_2k_fused_xent"),
                             ("pallas", True, "lm_2k_pallas_fusedxent")):
        log(f"compiling {tag}...")
        step, state, batch = _lm_step(2048, 8, attn, fused, repl)
        compiled = jax.jit(step).lower(state, batch).compile()
        record(_analyze(compiled, tag,
                        {"batch": 8, "seq": 2048, "attn": attn}))


def lm_8k():
    mesh = _topo_mesh(n=1)
    repl = NamedSharding(mesh, P())
    for attn in ("xla", "pallas"):
        tag = f"lm_8k_{attn}_attn"
        log(f"compiling {tag}...")
        try:
            step, state, batch = _lm_step(8192, 2, attn, True, repl)
            compiled = jax.jit(step).lower(state, batch).compile()
            record(_analyze(compiled, tag, {"batch": 2, "seq": 8192}))
        except Exception as e:  # noqa: BLE001
            record({"tag": tag, "batch": 2, "seq": 8192,
                    "compile_error": str(e)[:300]})


def bert_b256():
    """BERT-base classification step at b=256 s=128 — the queue-4 on-chip
    A/B's byte/residency picture, available offline.  BERT_LARGE=1
    compiles the 24-layer/1024-hidden large variant at b=128 instead
    (model-scale headroom evidence: the reference genre's next size up)."""
    from tpuframe.models import bert as bert_lib
    from tpuframe.models import losses
    from tpuframe.parallel import step as step_lib

    mesh = _topo_mesh(n=1)
    repl = NamedSharding(mesh, P())
    large = os.environ.get("BERT_LARGE") == "1"
    if large:
        cfg = bert_lib.BertConfig(dtype="bfloat16", hidden_size=1024,
                                  num_layers=24, num_heads=16,
                                  intermediate_size=4096)
        B, S = 128, 128
    else:
        cfg = bert_lib.BertConfig(dtype="bfloat16")
        B, S = 256, 128
    model = bert_lib.BertForSequenceClassification(cfg)
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=repl)
    lab = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, S), jnp.int32),
                             jnp.ones((1, S), jnp.int32),
                             jnp.zeros((1, S), jnp.int32)),
        jax.random.key(0))
    tx = optax.adamw(2e-5)

    def loss_fn(params, model_state, b, rng):
        logits = model.apply({"params": params}, b["input_ids"],
                             b["attention_mask"], b["token_type_ids"],
                             train=True, rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, b["label"]), ({}, {})

    state = to_shape_structs(jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx), variables),
        repl)
    step = step_lib.make_train_step(loss_fn, tx, None, donate=False)
    batch = {"input_ids": ids, "attention_mask": ids,
             "token_type_ids": ids, "label": lab}
    tag = "bert_large_b128" if large else "bert_b256"
    log(f"compiling {tag} s=128...")
    compiled = jax.jit(step).lower(state, batch).compile()
    record(_analyze(compiled, tag, {"batch": B, "seq": S}))


def dp32():
    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import step as step_lib

    from tpuframe.parallel import mesh as mesh_lib

    # TOPO=v4:2x2x4 compiles the same program against the v4-32 north
    # star (16 chips x 2 TensorCores = 32 devices, BASELINE.json:5).
    topo = topologies.get_topology_desc(
        os.environ.get("TOPO", "v5e:4x8"), platform="tpu")
    n = len(topo.devices)
    # The framework mesh (all six axes; only data sized) so the step's
    # default batch partition P(('data','fsdp')) resolves.
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n),
                              devices=list(topo.devices))
    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, mesh_lib.batch_spec())
    log(f"dp32: {n} compile-only devices")

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
        jax.random.key(0))
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"])
        return loss, (dict(mutated), {})

    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx, model_state={"batch_stats": v["batch_stats"]}),
        variables)
    state = to_shape_structs(state, repl)
    # Per-chip batch 8 keeps the compile tractable; collective payloads
    # depend on the GRADIENT tree, not the batch size.
    batch = {"image": jax.ShapeDtypeStruct((8 * n, 224, 224, 3),
                                           jnp.bfloat16, sharding=dsh),
             "label": jax.ShapeDtypeStruct((8 * n,), jnp.int32,
                                           sharding=dsh)}
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False)
    log("compiling the 32-device DP step (this is the big one)...")
    compiled = jax.jit(step).lower(state, batch).compile()
    txt = compiled.as_text()

    # Sum all-reduce payloads from the TPU lowering (shared parser —
    # pinned by tests/test_offline_ab_parser.py).
    from _hlo_parse import allreduce_payload

    payload, ops = allreduce_payload(txt)
    from _common import topo_tag_suffix

    record(_analyze(compiled, "resnet50_dp32" + topo_tag_suffix(
        os.environ.get("TOPO", "v5e:4x8"), "v5e:4x8"), {
        "devices": n, "allreduce_ops": ops,
        "allreduce_payload_mb": round(sum(payload.values()) / 1e6, 2),
        "payload_bf16_mb": round(payload["bf16"] / 1e6, 2),
        "payload_f32_mb": round(payload["f32"] / 1e6, 2),
        "grad_tree_f32_mb": 102.4}))


def remat_ab():
    """Donated ResNet-50 b=512 train step per tpuframe.mem remat policy —
    the same program tune's ``remat_sweep`` scores, as A/B rows (one
    ``policy`` column per line; ~4 min compile each)."""
    from tpuframe.tune import search as tune_search

    topo = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    raw = os.environ.get("REMAT_POLICIES", "none,dots,per_block")
    policies = tuple(p.strip() for p in raw.split(",") if p.strip())
    for pol in policies:
        log(f"compiling resnet50_remat_b512 policy={pol}...")
        try:
            compiled, _ = tune_search._remat_step_compile(
                topo.devices, 512, pol)
            record(_analyze(compiled, "resnet50_remat_b512",
                            {"batch": 512, "policy": pol}))
        except Exception as e:  # noqa: BLE001 — e.g. `full` OOMs the v5e
            record({"tag": "resnet50_remat_b512", "batch": 512,
                    "policy": pol, "compile_error": str(e)[:300]})


def show():
    """Print the SURVIVING rows (supersession rule in _ab_rows: latest
    line per tag wins — §11 regenerations hide the round-4 rows)."""
    from _ab_rows import load_rows, superseded_count

    rows = load_rows(OUT)
    dropped = superseded_count(open(OUT).read().strip().splitlines())
    log(f"{len(rows)} surviving row(s), {dropped} superseded")
    for row in rows:
        print(json.dumps(row))


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    steps = {"lm_xent": lm_xent, "lm_8k": lm_8k, "dp32": dp32,
             "bert_b256": bert_b256, "remat": remat_ab}
    if which == "show":
        return show()
    if which == "all":
        for name, fn in steps.items():
            log(f"=== {name} ===")
            fn()
    else:
        steps[which]()


if __name__ == "__main__":
    main()
