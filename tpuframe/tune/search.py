"""Candidate enumeration + the offline AOT sweep driver.

The sweep compiles every candidate on a compile-only TPU topology
(``jax.experimental.topologies.get_topology_desc``, PERF.md §7) on the CPU
host — real XLA:TPU lowering, real ``cost_analysis``/``memory_analysis``,
no chip — scores each with the roofline tables, and writes the
ranked results into the persistent tuning DB plus a human-readable report.

Candidate axes:

  - flash-attention block sizes (``TPUFRAME_FA_BLOCK_Q/K``), pruned against
    the Mosaic VMEM double-buffer budget BEFORE compiling — the §11 v4
    lesson: Mosaic double-buffers every grid-blocked operand, and the real
    compiler rejects tilings the interpret-mode tests happily accept.
  - ``TPUFRAME_XLA_OPTS`` compiler-option sets (latency-hiding scheduler,
    scoped vmem, all-reduce combiner thresholds via parallel/tuning.py's
    flag templates) applied through per-compile ``compiler_options`` —
    they travel inside the compile request, so no XLA_FLAGS env mutation
    (which TF106 now lints) is ever needed.
  - batch shapes for the bench ResNet-50 step.
  - rematerialization policies (``tpuframe.mem`` registry names) for the
    donated ResNet-50 train step, ranked on ``cost_analysis`` bytes
    accessed against the PERF.md §6 HBM touch model (``remat_sweep``).

jax is imported lazily inside functions: the candidate enumeration + VMEM
model are pure and feed the fast test tier.
"""

from __future__ import annotations

import fcntl
import json
import os
import sys

from tpuframe.tune import db as tune_db
from tpuframe.tune import roofline

# §11: fused_conv_bn budgets 10 MB for its single blocked operand pair;
# flash-attention runs three kernels with up to 8 blocked refs each, and
# v5e VMEM is 128 MiB/core — 16 MiB per kernel twin-buffer set more than
# clears compilation while leaving headroom for Mosaic's own spills.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

def fa_vmem_bytes(block_q: int, block_k: int, head_dim: int, *,
                  dtype_bytes: int = 2) -> int:
    """Worst-kernel VMEM estimate for one explicit (block_q, block_k) of
    the flash-attention fwd/bwd kernel trio: the kernels' own arithmetic
    (``ops/flash_attention.vmem_bytes``) at the sub-blocks they would take,
    so the sweep prunes by what the kernels hold and not by a copy of it."""
    from tpuframe.ops import flash_attention as fa

    tiling = fa._tiling(block_q, block_k, head_dim, dtype_bytes,
                        block_q, block_k)
    return max(fa.vmem_bytes(kernel, tiles, head_dim, dtype_bytes)
               for kernel, tiles in zip(fa._KERNELS, tiling))


def fa_block_candidates(seq_len: int, head_dim: int, *,
                        blocks=(128, 256, 512),
                        budget: int = DEFAULT_VMEM_BUDGET):
    """(kept, pruned) candidate lists.  Each entry:
    {"fa_block_q", "fa_block_k", "vmem_bytes"}.  Pruning happens HERE,
    before any compile is attempted — over-budget tilings and tilings the
    kernel's static grid cannot express (seq not divisible) never reach
    the compiler."""
    kept, pruned = [], []
    for bq in blocks:
        for bk in blocks:
            cand = {"fa_block_q": bq, "fa_block_k": bk,
                    "vmem_bytes": fa_vmem_bytes(bq, bk, head_dim)}
            if seq_len % bq or seq_len % bk:
                cand["pruned"] = "seq_not_divisible"
                pruned.append(cand)
            elif cand["vmem_bytes"] > budget:
                cand["pruned"] = "vmem_over_budget"
                pruned.append(cand)
            else:
                kept.append(cand)
    return kept, pruned


def fa_analytic_cost(seq: int, head_dim: int, heads: int, batch: int,
                     block_q: int, block_k: int, *, causal: bool = True,
                     dtype_bytes: int = 2):
    """Touch-model (flops, bytes) for the flash fwd+bwd kernel trio, used
    when the kernel cannot compile in the host's jax (SKIP-not-PASS: the
    record says ``source: analytic``, never passing itself off as compiler
    output).  Matmul work: fwd QK^T + PV (4*e*s), bwd dV/dP/dS/dQ/dK
    (10*e*s); the causal trichotomy skips ~half the blocks.  HBM touches:
    streamed operands re-read once per opposing block row (fwd+dq stream
    K/V seq/block_q times, dkv streams Q/dO seq/block_k times), residents
    once — so bigger blocks mean fewer re-reads, the axis the analytic
    ranking actually discriminates on."""
    e = batch * seq * heads * head_dim
    frac = 0.5 if causal else 1.0
    flops = frac * 14.0 * e * seq
    n_q, n_k = seq // block_q, seq // block_k
    bytes_accessed = dtype_bytes * e * (6 + frac * (4 * n_q + 2 * n_k))
    return flops, bytes_accessed


def xla_opts_candidate_sets() -> list:
    """Named ``compiler_options`` dicts for the sweep.  The combiner
    threshold reuses parallel/tuning.py's flag template (single source for
    the flag spelling) converted from --flag=v to option form."""
    from tpuframe.parallel import tuning

    combiner = {}
    for flag in tuning.fusion_flags(64 * 1024 * 1024):
        k, _, v = flag.lstrip("-").partition("=")
        combiner[k] = v
    return [
        ("baseline", {}),
        ("latency_hiding",
         {"xla_tpu_enable_latency_hiding_scheduler": "true"}),
        ("scoped_vmem_64m",
         {"xla_tpu_scoped_vmem_limit_kib": "65536"}),
        ("combine_64m", combiner),
    ]


def remat_policy_candidates() -> tuple:
    """The remat policies the offline sweep scores.  Every entry is a
    :mod:`tpuframe.mem` registry name, so a sweep winner written to the DB
    is directly consumable by ``TPUFRAME_REMAT_POLICY``/``mem.resolve``.

    ``everything`` is omitted: under ``jax.checkpoint`` it saves every
    residual the un-wrapped program saves, so its compiled step is
    byte-identical to ``none`` and would only double the (4-minute) compile
    bill for a guaranteed tie."""
    return ("none", "dots", "dots_no_batch", "per_block",
            "save_named(block_out)", "full")


# ---------------------------------------------------------------------------
# AOT lock (same lockfile as perf/_common.hold_aot_lock — libtpu ABORTS when
# two compile-only processes initialize concurrently, so the tuner and the
# census scripts must serialize against each other)
# ---------------------------------------------------------------------------

_AOT_LOCK_HANDLE = None


def hold_aot_lock() -> None:
    global _AOT_LOCK_HANDLE
    if _AOT_LOCK_HANDLE is not None:
        return
    fh = open(os.path.join(tune_db.repo_root(), ".aot_compile.lock"), "w")
    fcntl.flock(fh, fcntl.LOCK_EX)  # blocks until the current holder exits
    _AOT_LOCK_HANDLE = fh


def _log(msg, log=None):
    (log or (lambda m: print(f"[tune] {m}", file=sys.stderr, flush=True)))(msg)


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------


def _fa_compile(topo_devices, seq, head_dim, heads, batch, bq, bk):
    """AOT-compile flash-attention fwd+bwd at one tiling; returns the
    compiled object + a stable program desc for fingerprinting."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpuframe.ops import flash_attention as fa

    # Single-device topology probe, not a training mesh — no axis-name
    # contract to honour.
    mesh = Mesh(np.array(topo_devices[:1]), ("d",))  # tf-lint: ok[TF119]
    repl = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((batch, seq, heads, head_dim), jnp.bfloat16,
                             sharding=repl)

    def fwd(q, k, v):
        out = fa.flash_mha(q, k, v, causal=True, block_q=bq, block_k=bk,
                           interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    lowered = jax.jit(jax.grad(fwd, argnums=(0, 1, 2))).lower(x, x, x)
    compiled = lowered.compile()
    text = compiled.as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("flash kernel did not lower to a Mosaic custom "
                           "call — interpret mode leaked in (§11)")
    desc = {"program": f"flash_mha_s{seq}_d{head_dim}",
            "shape": list(x.shape), "causal": True,
            "block_q": bq, "block_k": bk}
    return compiled, desc


def _bench_step_compile(topo_devices, batch_per_chip, xla_opts):
    """AOT-compile the bench ResNet-50 train step (the program bench.py
    runs) over the full topology with one compiler-option set."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    n = len(topo_devices)
    # The framework mesh (all six axes, only data sized) so the step's
    # default batch partition P(('data','fsdp')) resolves — same idiom as
    # perf/exp_offline_ab.dp32.
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=n),
                              devices=list(topo_devices))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, mesh_lib.batch_spec())
    global_batch = batch_per_chip * n

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
        jax.random.key(0))
    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx,
            model_state={"batch_stats": v["batch_stats"]}), variables)

    def _repl(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
            tree)

    state = _repl(state)
    batch = {"image": jax.ShapeDtypeStruct(
                 (global_batch, 224, 224, 3), jnp.bfloat16, sharding=data),
             "label": jax.ShapeDtypeStruct(
                 (global_batch,), jnp.int32, sharding=data)}

    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                    compiler_options=xla_opts or None)
    lowered = step.lower(state, batch)
    compiled = lowered.compile()
    desc = {"program": f"bench_resnet50_b{batch_per_chip}",
            "n_chips": n, "global_batch": global_batch}
    return compiled, desc


def _remat_step_compile(topo_devices, batch, remat_policy):
    """AOT-compile the DONATED ResNet-50 train step on ONE compile-only
    device under one remat policy.  Single-chip + global batch so the
    bytes-accessed totals line up with the PERF.md §2 anchor (1.435e11 B at
    b=512) and the §6 touch model; donation matches what train.py/bench.py
    actually run, unlike the bench sweep's donate=False A/B rig."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=1),
                              devices=list(topo_devices[:1]))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, mesh_lib.batch_spec())

    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

    def loss_fn(params, model_state, batch, step_rng):
        logits, mutated = model.apply(
            {"params": params, **model_state}, batch["image"], train=True,
            mutable=["batch_stats"])
        loss = losses.softmax_cross_entropy(logits, batch["label"],
                                            label_smoothing=0.1)
        return loss, (dict(mutated), {})

    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
        jax.random.key(0))
    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(
            v["params"], tx,
            model_state={"batch_stats": v["batch_stats"]}), variables)

    def _repl(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
            tree)

    state = _repl(state)
    batch_structs = {
        "image": jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.bfloat16,
                                      sharding=data),
        "label": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data)}

    step = step_lib.make_train_step(
        loss_fn, tx, mesh, donate=True,
        remat_policy=None if remat_policy == "none" else remat_policy)
    compiled = step.lower(state, batch_structs).compile()
    desc = {"program": f"train_resnet50_b{batch}", "n_chips": 1,
            "global_batch": batch, "donate": True,
            "remat_policy": remat_policy}
    return compiled, desc


def remat_sweep(topology: str = "v5e:2x2", *, db_path: str | None = None,
                report_path: str | None = None, batch: int = 512,
                policies=None, log=None) -> dict:
    """Offline remat-policy search: AOT-compile the donated ResNet-50
    train step once per :mod:`tpuframe.mem` policy, rank on
    ``cost_analysis`` bytes accessed (the §6 HBM-traffic objective — this
    program is bandwidth-bound, so bytes IS the step-time lever), persist
    every candidate to the tuning DB, and write a report with each
    policy's bytes delta vs ``none``."""
    import jax  # noqa: F401 — fail fast before holding the lock
    from jax.experimental import topologies

    from tpuframe import mem

    policies = tuple(policies or remat_policy_candidates())
    for pol in policies:
        mem.validate_policy(pol)  # typo'd candidate fails before the lock

    hold_aot_lock()
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    topo = topologies.get_topology_desc(topology, platform="tpu")
    _log(f"remat sweep on {topology}: {len(policies)} policies, "
         f"ResNet-50 b={batch} donated train step", log)

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    program = f"train_resnet50_b{batch}"
    report = {"topology": topology, "generation": gen, "batch": batch,
              "objective": "bytes_accessed",
              "remat": {"rows": [], "compile_errors": []}}

    baseline_bytes = None
    for pol in policies:
        try:
            compiled, desc = _remat_step_compile(topo.devices, batch, pol)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            row = {"policy": pol,
                   "error": f"{type(e).__name__}: {e}"[:300]}
            report["remat"]["compile_errors"].append(row)
            _log(f"  remat {pol}: COMPILE ERROR {row['error'][:80]}", log)
            continue
        pred = roofline.score_compiled(compiled, gen)
        pred["source"] = "compiled"
        temp_gb = None
        try:
            temp_gb = round(
                compiled.memory_analysis().temp_size_in_bytes / 1e9, 2)
        except Exception:  # noqa: BLE001 — best-effort, like score_compiled
            pass
        if pol == "none":
            baseline_bytes = pred["bytes"]
        drop = None
        if baseline_bytes:
            drop = round(100.0 * (1.0 - pred["bytes"] / baseline_bytes), 1)
        pred["bytes_drop_vs_none_pct"] = drop
        db.add({"program": program, "family": "remat_resnet50",
                "fingerprint": tune_db.fingerprint(desc),
                "topology": topology, "generation": gen,
                "config": {"remat_policy": pol, "batch": batch},
                "predicted": pred})
        row = {"policy": pol, "gb": round(pred["bytes"] / 1e9, 2),
               "tflops": round(pred["flops"] / 1e12, 2),
               "predicted_ms": pred["predicted_ms"], "bound": pred["bound"],
               "temp_gb": temp_gb, "drop_vs_none_pct": drop}
        report["remat"]["rows"].append(row)
        _log(f"  remat {pol}: {row['gb']} GB accessed "
             f"({row['predicted_ms']} ms {row['bound']}-bound, "
             f"temp {temp_gb} GB, drop {drop}%)", log)

    # Rank on the sweep objective.  ``none`` compiles first, so every row
    # has its drop; re-derive drops if the caller reordered policies.
    rows = report["remat"]["rows"]
    if baseline_bytes:
        for row in rows:
            row["drop_vs_none_pct"] = round(
                100.0 * (1.0 - row["gb"] * 1e9 / baseline_bytes), 1)
    rows.sort(key=lambda r: r["gb"])
    report["winner"] = rows[0] if rows else None
    db.save()
    _log(f"tuning DB: {db.path} ({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"remat_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


def _zero1_step_compile(topo_devices, program: str, batch: int,
                        weight_update: str,
                        fusion_threshold: int | None = None,
                        slices: int = 1, hier: str = "flat"):
    """AOT-compile one donated train step over the FULL topology under one
    weight-update mode.  Unlike the remat sweep's single-chip rig, the
    collective swap is the whole point here — the reduce-scatter /
    all-gather pair only exists with every chip in the mesh.  With
    ``slices > 1`` the devices (from ``pspec.topology_devices``) are laid
    out on a hierarchical slice×data mesh so the hier sweep's two-level
    candidates lower their real cross-slice collectives.  Returns
    ``(compiled, desc, opt_state_bytes_per_chip, census)``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuframe import models
    from tpuframe.models import losses
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.parallel import step as step_lib
    from tpuframe.parallel import zero1 as zero1_lib

    n = len(topo_devices)
    if slices > 1 and n % slices:
        raise ValueError(f"{n} devices do not tile {slices} slices")
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec(data=n // max(slices, 1), slices=slices),
        devices=list(topo_devices))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, mesh_lib.batch_spec(mesh=mesh))

    if program == "resnet50":
        model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        tx = optax.sgd(0.1, momentum=0.9, nesterov=True)

        def loss_fn(params, model_state, batch, step_rng):
            logits, mutated = model.apply(
                {"params": params, **model_state}, batch["image"],
                train=True, mutable=["batch_stats"])
            loss = losses.softmax_cross_entropy(logits, batch["label"],
                                                label_smoothing=0.1)
            return loss, (dict(mutated), {})

        variables = jax.eval_shape(
            lambda k: model.init(
                k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16)),
            jax.random.key(0))
        model_state = {"batch_stats": variables["batch_stats"]}
        batch_structs = {
            "image": jax.ShapeDtypeStruct((batch, 224, 224, 3),
                                          jnp.bfloat16, sharding=data),
            "label": jax.ShapeDtypeStruct((batch,), jnp.int32,
                                          sharding=data)}
    elif program == "bert":
        model = models.get_model("bert-base", num_classes=2)
        tx = optax.adamw(2e-5)  # the GLUE fine-tune recipe — 2 moments

        def loss_fn(params, model_state, batch, step_rng):
            logits = model.apply(
                {"params": params}, batch["input_ids"], train=True,
                rngs={"dropout": step_rng})
            loss = losses.softmax_cross_entropy(logits, batch["label"])
            return loss, (model_state, {})

        variables = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((2, 128), jnp.int32)),
            jax.random.key(0))
        model_state = {}
        batch_structs = {
            "input_ids": jax.ShapeDtypeStruct((batch, 128), jnp.int32,
                                              sharding=data),
            "label": jax.ShapeDtypeStruct((batch,), jnp.int32,
                                          sharding=data)}
    elif program == "lm":
        # A mid-size TransformerLM (~3.8M params, ~15 MB of f32 grads on
        # the wire) — big enough that every fabric column in the hier
        # sweep carries honest megabytes, small enough that the
        # compile-only multi-slice lowering stays in seconds where the
        # conv stack costs ~4 min per candidate (resnet50) and BERT's
        # 110M-param step takes longer still on this backend.
        seq = 128
        model = models.get_model(
            "transformer-lm", tiny=True, vocab_size=2048, max_seq=seq,
            hidden_size=256, num_layers=4, num_heads=8,
            intermediate_size=1024)
        tx = optax.adamw(1e-3)

        def loss_fn(params, model_state, batch, step_rng):
            logits = model.apply(
                {"params": params}, batch["input_ids"], train=True,
                rngs={"dropout": step_rng})
            loss = losses.softmax_cross_entropy(logits, batch["labels"])
            return loss, (model_state, {})

        variables = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((2, seq), jnp.int32)),
            jax.random.key(0))
        model_state = {}
        ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
        batch_structs = {"input_ids": ids, "labels": ids}
    else:
        raise ValueError(f"unknown zero1 sweep program {program!r}")

    params = variables["params"]
    state = jax.eval_shape(
        lambda v: step_lib.TrainState.create(v["params"], tx,
                                             model_state=model_state),
        variables)

    census = zero1_lib.padding_census(params, n)
    if weight_update == "zero1":
        opt_state = jax.eval_shape(
            lambda p: zero1_lib.init_opt_state(tx, p, n), params)
        state = dataclasses.replace(state, opt_state=opt_state)
        shardings = zero1_lib.state_shardings(state, mesh)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            state, shardings)
        opt_bytes = sum(
            s.size * s.dtype.itemsize
            for s in jax.tree.leaves(opt_state)) // n
    else:
        state = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=repl), state)
        opt_bytes = sum(s.size * s.dtype.itemsize
                        for s in jax.tree.leaves(state.opt_state))

    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=True,
                                    weight_update=weight_update,
                                    fusion_threshold=fusion_threshold,
                                    hier=hier)
    lowered = step.lower(state, batch_structs)
    if fusion_threshold is not None:
        # The staged pass owns bucketing: hand the XLA all-reduce
        # combiner off per-compile (strategies._overlap_compile_opts —
        # same contract).  Honored where the generic DebugOptions field
        # is read (CPU XLA); the v5e libtpu pin accepts-but-ignores it
        # and re-merges the buckets regardless, which is why the sweep's
        # thresholds tie on that backend (PERF.md §26).
        compiled = lowered.compile(compiler_options={
            "xla_gpu_all_reduce_combine_threshold_bytes": 0})
    else:
        compiled = lowered.compile()
    desc = {"program": f"train_{program}_b{batch}", "n_chips": n,
            "global_batch": batch, "donate": True,
            "weight_update": weight_update}
    if fusion_threshold is not None:
        desc["fusion_threshold"] = int(fusion_threshold)
    # Only stamp the hierarchical fields on multi-slice compiles so the
    # single-slice sweeps' fingerprints stay byte-identical to the DB
    # rows they already persisted.
    if slices > 1:
        desc["slices"] = int(slices)
        desc["hier"] = hier
    return compiled, desc, opt_bytes, census


def zero1_sweep(topology: str = "v5e:2x2", *, db_path: str | None = None,
                report_path: str | None = None, batch: int = 512,
                bert_batch: int = 256, log=None) -> dict:
    """Offline weight-update sharding search: AOT-compile the donated
    ResNet-50 and BERT train steps once per ``tpuframe.parallel.zero1``
    mode over the full topology, rank on ``cost_analysis`` bytes accessed
    plus per-chip optimizer-state HBM residency, and persist every
    candidate to the ``weight_update_*`` DB families.  ZeRO-1
    (arXiv:2004.13336) trades the all-reduce for a reduce-scatter +
    all-gather at equal wire bytes; the win it is searched for here is the
    (n-1)/n cut in optimizer-state residency and the update-math HBM
    traffic that goes with it."""
    import jax  # noqa: F401 — fail fast before holding the lock
    from jax.experimental import topologies

    hold_aot_lock()
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    topo = topologies.get_topology_desc(topology, platform="tpu")
    n = len(topo.devices)
    programs = (("resnet50", batch), ("bert", bert_batch))
    _log(f"zero1 sweep on {topology} ({n} chips): "
         f"{[p for p, _ in programs]} x ('replicated', 'zero1')", log)

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    report = {"topology": topology, "generation": gen, "n_chips": n,
              "objective": "bytes_accessed + opt_state_residency",
              "weight_update": {"rows": [], "compile_errors": [],
                                "padding_census": {}}}

    for program, b in programs:
        baseline = {}
        for mode in ("replicated", "zero1"):
            try:
                compiled, desc, opt_bytes, census = _zero1_step_compile(
                    topo.devices, program, b, mode)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                row = {"program": program, "weight_update": mode,
                       "error": f"{type(e).__name__}: {e}"[:300]}
                report["weight_update"]["compile_errors"].append(row)
                _log(f"  {program}/{mode}: COMPILE ERROR "
                     f"{row['error'][:80]}", log)
                continue
            pred = roofline.score_compiled(compiled, gen)
            pred["source"] = "compiled"
            temp_gb = None
            try:
                temp_gb = round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e9, 2)
            except Exception:  # noqa: BLE001 — best-effort
                pass
            if mode == "replicated":
                baseline = {"bytes": pred["bytes"], "opt": opt_bytes}
                report["weight_update"]["padding_census"][program] = {
                    "total_param_bytes": census["total_bytes"],
                    "padded_bytes": census["padded_bytes"],
                    "waste_frac": census["waste_frac"],
                    "n_shards": n}
            row = {"program": program, "weight_update": mode,
                   "global_batch": b,
                   "gb": round(pred["bytes"] / 1e9, 3),
                   "predicted_ms": pred["predicted_ms"],
                   "bound": pred["bound"], "temp_gb": temp_gb,
                   "opt_state_resident_mb": round(opt_bytes / 1e6, 2)}
            if baseline.get("opt"):
                row["opt_residency_drop_pct"] = round(
                    100.0 * (1.0 - opt_bytes / baseline["opt"]), 1)
            if baseline.get("bytes"):
                row["bytes_drop_vs_replicated_pct"] = round(
                    100.0 * (1.0 - pred["bytes"] / baseline["bytes"]), 1)
            pred["opt_state_resident_bytes"] = int(opt_bytes)
            db.add({"program": desc["program"],
                    "family": f"weight_update_{program}",
                    "fingerprint": tune_db.fingerprint(desc),
                    "topology": topology, "generation": gen,
                    "config": {"weight_update": mode, "batch": b},
                    "predicted": pred})
            report["weight_update"]["rows"].append(row)
            _log(f"  {program}/{mode}: {row['gb']} GB accessed "
                 f"({row['predicted_ms']} ms {row['bound']}-bound), "
                 f"opt state {row['opt_state_resident_mb']} MB/chip", log)

    rows = report["weight_update"]["rows"]
    winners = {}
    for program, _ in programs:
        prog_rows = [r for r in rows if r["program"] == program]
        prog_rows.sort(key=lambda r: (r["predicted_ms"] or float("inf"),
                                      r["opt_state_resident_mb"]))
        if prog_rows:
            winners[program] = prog_rows[0]
    report["winners"] = winners
    db.save()
    _log(f"tuning DB: {db.path} ({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"zero1_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


def hier_sweep(topology: str = "v5e:2x2", *, slices: int = 2,
               db_path: str | None = None, report_path: str | None = None,
               batch: int = 512, zero1_batch: int = 256, log=None) -> dict:
    """Offline two-level-collective search: AOT-compile the donated
    TransformerLM train step (plain DP and ZeRO-1 arms — see the ``lm``
    program note in ``_zero1_step_compile`` for why not the conv/BERT
    pair the other sweeps use) on a compile-only MULTI-SLICE topology
    (``pspec.topology_devices`` — PJRT ``num_slices``, no chip needed)
    once per lowering (flat, hier), attribute every
    collective's wire bytes to its fabric
    with shardflow's replica-group splitter, price the two columns with
    ``roofline.comm_split_score`` (ICI over the device ring, DCN over
    the slice ring — the ~32x bandwidth gap is the whole game), and
    persist every candidate to the ``hier_collectives`` DB family.

    Candidates: flat (the baseline everything is ratioed against) and
    hier (PERF §23's two-level lowering — DCN carries 1/n_inner of the
    bytes).

    DB rows store the comm-aware total (step + ICI + DCN ms) as their
    ``predicted_ms`` so ``db.best`` / ``resolve_hier`` elect the
    candidate the split model actually favors — the raw roofline step
    time ties across hier modes by construction (same compute), and a
    tie would elect noise.

    Each candidate compiles in its OWN worker subprocess
    (``python -m tpuframe.tune _hier-probe``): the compile-only
    multi-slice backend's compiles are nondeterministically slow — the
    same candidate that compiles in seconds in one run can wedge libtpu
    for tens of minutes in the next — and isolation plus a timeout
    turns a wedged compile into a retried (then recorded) row instead
    of hanging the whole sweep."""
    import subprocess
    import tempfile

    import jax  # noqa: F401 — fail fast before holding the lock

    hold_aot_lock()
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    n = roofline.n_chips_from_topology(topology) * max(int(slices), 1)
    candidates = ("flat", "hier")
    configs = (("lm", batch, "replicated"),
               ("lm", zero1_batch, "zero1"))
    _log(f"hier sweep on {topology} x{slices} slices ({n} chips): "
         f"{[(p, m) for p, _, m in configs]} x {list(candidates)}", log)

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    report = {"topology": topology, "slices": slices, "generation": gen,
              "n_chips": n,
              "objective": "t_step_ms + t_ici_ms + t_dcn_ms "
                           "(comm_split_score on shardflow's "
                           "replica-group fabric attribution)",
              "hier": {"rows": [], "compile_errors": []}}

    for program, b, mode in configs:
        baseline = {}
        for hier_mode in candidates:
            payload, err, rc = None, None, 0
            for attempt in (1, 2):
                with tempfile.NamedTemporaryFile(suffix=".json",
                                                 delete=False) as tf:
                    out_path = tf.name
                cmd = [sys.executable, "-m", "tpuframe.tune",
                       "_hier-probe", "--topology", topology,
                       "--slices", str(slices), "--program", program,
                       "--batch", str(b), "--mode", mode,
                       "--hier", hier_mode, "--out", out_path]
                try:
                    proc = subprocess.run(cmd, capture_output=True,
                                          text=True, timeout=480)
                    rc, stderr = proc.returncode, proc.stderr
                except subprocess.TimeoutExpired:
                    rc, stderr = -1, "probe timed out after 480 s"
                try:
                    if rc == 0:
                        with open(out_path) as f:
                            payload = json.load(f)
                        break
                    err = _crash_reason(stderr, rc)
                    if rc != -1:
                        break  # deterministic failure — retry won't help
                    _log(f"  {program}/{hier_mode}: wedged compile "
                         f"(attempt {attempt}), "
                         + ("retrying" if attempt == 1 else "giving up"),
                         log)
                finally:
                    if os.path.exists(out_path):
                        os.unlink(out_path)
            if payload is None:
                row = {"program": program, "hier": hier_mode,
                       "weight_update": mode,
                       "returncode": rc, "error": err}
                report["hier"]["compile_errors"].append(row)
                _log(f"  {program}/{hier_mode}: COMPILE ERROR "
                     f"{(err or '')[:80]}", log)
                continue
            row, desc, pred = (payload["row"], payload["desc"],
                               payload["pred"])
            css = pred["comm_split"]
            total_ms = row["predicted_total_ms"]
            if hier_mode == "flat":
                baseline = {"dcn_bytes": css["dcn_bytes"],
                            "t_dcn_ms": css["t_dcn_ms"],
                            "total_ms": total_ms}
            if baseline.get("dcn_bytes"):
                row["dcn_bytes_ratio_vs_flat"] = round(
                    css["dcn_bytes"] / baseline["dcn_bytes"], 4)
            if baseline.get("t_dcn_ms"):
                row["t_dcn_ratio_vs_flat"] = round(
                    css["t_dcn_ms"] / baseline["t_dcn_ms"], 4)
            db.add({"program": desc["program"],
                    "family": "hier_collectives",
                    "fingerprint": tune_db.fingerprint(desc),
                    "topology": topology, "generation": gen,
                    "config": {"hier": hier_mode,
                               "batch": b, "weight_update": mode,
                               "slices": slices},
                    "predicted": pred})
            report["hier"]["rows"].append(row)
            _log(f"  {program}/{hier_mode}: "
                 f"{row['predicted_total_ms']} ms total "
                 f"({row['t_step_ms']} step + {row['t_ici_ms']} ICI + "
                 f"{row['t_dcn_ms']} DCN), "
                 f"{css['dcn_bytes'] / 1e6:.2f} MB on DCN", log)

    rows = report["hier"]["rows"]
    winners = {}
    for program, _, mode in configs:
        arm_rows = [r for r in rows if r["program"] == program
                    and r["weight_update"] == mode]
        arm_rows.sort(
            key=lambda r: r.get("predicted_total_ms") or float("inf"))
        if arm_rows:
            winners[f"{program}/{mode}"] = arm_rows[0]
    report["winners"] = winners
    db.save()
    _log(f"tuning DB: {db.path} ({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"hier_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


def _hier_probe_row(topology: str, slices: int, program: str, batch: int,
                    mode: str, hier: str) -> dict:
    """Compile + score ONE two-level-collective candidate; returns the
    report row, its DB descriptor, and the comm-aware predicted dict as
    one JSON payload.

    Runs inside a worker subprocess spawned by ``hier_sweep`` (see its
    docstring for why isolation).  The parent holds the AOT lock; this
    helper must not re-take it."""
    from tpuframe.analysis import collective_graph as cg
    from tpuframe.analysis import hlo_audit, shardflow
    from tpuframe.parallel import pspec

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    devices = pspec.topology_devices(topology, slices=slices)
    n = len(devices)
    compiled, desc, _opt_bytes, _census = _zero1_step_compile(
        devices, program, batch, mode, slices=slices, hier=hier)
    hlo = compiled.as_text()
    pred = roofline.score_compiled(compiled, gen)
    pred["source"] = "compiled"
    coll = hlo_audit.parse_collectives(hlo)
    split = shardflow.comm_split(
        cg.parse_graph(hlo), coll.filter(1024),
        mesh_shape={"slice": slices, "data": n // slices}, n_devices=n)
    # The TPU backend routes the cross-slice hop through the MegaScale
    # transport (host-transfer send/recv), not HLO collectives — fold
    # those bytes into the DCN column or the sweep scores DCN as free.
    for kind, nbytes in shardflow.megascale_split(hlo).items():
        split["dcn"][kind] = split["dcn"].get(kind, 0) + int(nbytes)
    css = roofline.comm_split_score(gen, split, n_devices=n,
                                    n_slices=slices)
    total_ms = round(pred["predicted_ms"] + css["t_ici_ms"]
                     + css["t_dcn_ms"], 3)
    pred["comm_split"] = css
    pred["t_step_ms"] = pred["predicted_ms"]
    pred["predicted_ms"] = total_ms  # comm-aware rank (see hier_sweep)
    row = {"program": program, "hier": hier, "weight_update": mode,
           "global_batch": batch,
           "t_step_ms": pred["t_step_ms"],
           "t_ici_ms": css["t_ici_ms"],
           "t_dcn_ms": css["t_dcn_ms"],
           "predicted_total_ms": total_ms,
           "ici_bytes": css["ici_bytes"],
           "dcn_bytes": css["dcn_bytes"], "bound": pred["bound"]}
    return {"row": row, "desc": desc, "pred": pred}


def _fusion_probe_row(topology: str, program: str, batch: int,
                      threshold: int | None, floor: int) -> dict:
    """Compile + score ONE fusion candidate and return its report row.

    Runs inside a worker subprocess spawned by ``fusion_sweep`` — a
    bucket shape can abort libtpu's fusion emitter outright (a CHECK
    failure in ``fusion_emitter.cc``, observed at 256 KiB+ buckets on
    the ResNet-50 step, PERF §26), and a SIGABRT in-process would take
    the whole sweep and its partial report down with it.  The parent
    holds the AOT lock; this helper must not re-take it."""
    from jax.experimental import topologies

    from tpuframe.analysis import collective_graph as cg
    from tpuframe.analysis import hlo_audit, shardflow

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    topo = topologies.get_topology_desc(topology, platform="tpu")
    n = len(topo.devices)
    compiled, _desc, _opt, _census = _zero1_step_compile(
        topo.devices, program, batch, "replicated",
        fusion_threshold=threshold)
    txt = compiled.as_text()
    pred = roofline.score_compiled(compiled, gen)
    coll = hlo_audit.parse_collectives(txt)
    comm = roofline.comm_score(gen, coll.filter(floor), n)
    total_ms = round(pred["predicted_ms"] + comm["t_ici_ms"], 3)
    graph = cg.parse_graph(txt)
    entry = shardflow.derive_schedule_entry(graph, ignore_below=floor)
    score = shardflow.overlap_score(graph, coll, n_devices=n,
                                    ignore_below=floor, generation=gen)
    return {"program": program, "fusion_threshold": threshold,
            "global_batch": batch,
            "collectives_above_floor": score["collectives_above_floor"],
            "comm_bytes": comm["comm_bytes"],
            "overlap_potential": score["overlap_potential"],
            "comm_ms": score["comm_ms"],
            "hideable_ms": score["hideable_ms"],
            "interleavable_bytes": entry["interleavable_bytes"],
            "async_pairs": entry["async_pairs"],
            "predicted_ms": pred["predicted_ms"],
            "t_ici_ms": comm["t_ici_ms"],
            "predicted_total_ms": total_ms}


def _crash_reason(stderr: str, returncode: int) -> str:
    """Condense a dead probe's stderr to the line that names the abort."""
    lines = [ln.strip() for ln in (stderr or "").splitlines() if ln.strip()]
    for ln in reversed(lines):
        if "Check failed" in ln or "CHECK failed" in ln:
            return ln[:300]
    for ln in reversed(lines):
        if "Error" in ln or "error" in ln:
            return ln[:300]
    tail = lines[-1][:200] if lines else ""
    return f"probe exited {returncode}" + (f": {tail}" if tail else "")


def fusion_sweep(topology: str = "v5e:2x2", *, db_path: str | None = None,
                 report_path: str | None = None, batch: int = 512,
                 thresholds=(16384, 32768, 65536, 131072, 262144),
                 log=None) -> dict:
    """Offline gradient-fusion bucket-threshold search: AOT-compile the
    donated ResNet-50 DP train step once per ``threshold_bytes`` over
    the full topology, rank on the schedule plane's ``overlap_score``
    (how much of each bucket's wire time has legally interleavable
    compute to hide behind it) plus the compiled wire bytes, and persist
    the winner to the ``fusion_threshold`` DB family.  Small buckets
    give the scheduler more interior windows but pay more per-collective
    latency; huge buckets degenerate to the end-of-backprop sync pack
    (one window, nothing left to overlap) — the sweep finds the knee.
    An unfused per-leaf baseline row rides along for comparison but is
    never the winner.

    Each candidate compiles in its OWN worker subprocess
    (``python -m tpuframe.tune _fusion-probe``): libtpu's fusion
    emitter can hard-abort (CHECK failure, SIGABRT) on some bucket
    shapes, and isolation turns a compiler crash into a recorded
    ``compile_errors`` row instead of losing the sweep."""
    import subprocess
    import tempfile

    import jax  # noqa: F401 — fail fast before holding the lock

    hold_aot_lock()
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    n = roofline.n_chips_from_topology(topology)
    floor = 1024  # fused_dp_budget's floor — every bucket counts
    program = "resnet50"
    _log(f"fusion sweep on {topology} ({n} chips): {program} dp x "
         f"{list(thresholds)} + unfused baseline", log)

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    report = {"topology": topology, "generation": gen, "n_chips": n,
              "objective": "overlap_potential desc, then wire bytes "
                           "and predicted_total_ms asc",
              "ignore_below": floor,
              "fusion": {"rows": [], "compile_errors": []}}

    candidates = [None] + [int(t) for t in thresholds]
    for threshold in candidates:
        tag = "unfused" if threshold is None else str(threshold)
        with tempfile.NamedTemporaryFile(suffix=".json",
                                         delete=False) as tf:
            out_path = tf.name
        cmd = [sys.executable, "-m", "tpuframe.tune", "_fusion-probe",
               "--topology", topology, "--program", program,
               "--batch", str(batch), "--floor", str(floor),
               "--out", out_path]
        if threshold is not None:
            cmd += ["--threshold", str(threshold)]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=1800)
            rc, stderr = proc.returncode, proc.stderr
        except subprocess.TimeoutExpired:
            rc, stderr = -1, "probe timed out after 1800 s"
        try:
            if rc == 0:
                with open(out_path) as f:
                    row = json.load(f)
                report["fusion"]["rows"].append(row)
                _log(f"  {program}/{tag}: overlap "
                     f"{row['overlap_potential']}, "
                     f"{row['collectives_above_floor']} collective(s) "
                     f"{row['comm_bytes'] / 1e6:.2f} MB, "
                     f"{row['predicted_total_ms']} ms total", log)
            else:
                err = {"program": program, "fusion_threshold": threshold,
                       "returncode": rc,
                       "error": _crash_reason(stderr, rc)}
                report["fusion"]["compile_errors"].append(err)
                _log(f"  {program}/{tag}: COMPILE CRASH (rc {rc}) "
                     f"{err['error'][:80]}", log)
        finally:
            if os.path.exists(out_path):
                os.unlink(out_path)

    fused_rows = [r for r in report["fusion"]["rows"]
                  if r["fusion_threshold"] is not None]
    fused_rows.sort(key=lambda r: (-(r["overlap_potential"] or 0.0),
                                   r["comm_bytes"],
                                   r["predicted_total_ms"]))
    if fused_rows:
        w = fused_rows[0]
        report["winner"] = w
        pred_w = {"predicted_ms": w["predicted_ms"],
                  "predicted_total_ms": w["predicted_total_ms"],
                  "overlap_potential": w["overlap_potential"],
                  "comm_bytes": w["comm_bytes"], "source": "compiled"}
        # One winner per program: db.add keys on config, so a re-sweep
        # electing a different threshold would otherwise leave the old
        # winner behind and make resolve_fusion_threshold ambiguous.
        db.data["records"] = [
            r for r in db.data["records"]
            if not (r.get("family") == "fusion_threshold"
                    and r.get("program") == f"train_{program}_b{batch}")]
        db.add({"program": f"train_{program}_b{batch}",
                "family": "fusion_threshold",
                "fingerprint": tune_db.fingerprint(
                    {"program": f"train_{program}_b{batch}",
                     "n_chips": n, "global_batch": batch}),
                "topology": topology, "generation": gen,
                "config": {"fusion_threshold": w["fusion_threshold"],
                           "batch": batch},
                "predicted": pred_w})
        db.save()
        _log(f"winner: threshold {w['fusion_threshold']} "
             f"(overlap {w['overlap_potential']}) -> {db.path} "
             f"({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"fusion_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


def sweep(topology: str = "v5e:2x2", *, db_path: str | None = None,
          report_path: str | None = None, seq: int = 2048,
          head_dim: int = 64, heads: int = 8, fa_batch: int = 4,
          blocks=(128, 256, 512), bench_batches=(256,),
          vmem_budget: int = DEFAULT_VMEM_BUDGET, log=None) -> dict:
    """Run the full offline sweep; returns the report dict (also written
    to ``report_path``) and persists every scored candidate into the DB."""
    import jax  # noqa: F401 — fail fast before holding the lock
    from jax.experimental import topologies

    hold_aot_lock()
    # off-GCP hosts: without this, libtpu's topology init polls the GCE
    # metadata server 30x per variable (minutes of 403s) before giving up
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    topo = topologies.get_topology_desc(topology, platform="tpu")
    _log(f"topology {topology}: {len(topo.devices)} compile-only devices",
         log)

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    report = {"topology": topology, "generation": gen,
              "fa": {"kept": [], "pruned": [], "compile_errors": []},
              "bench": {"rows": [], "compile_errors": []}}

    # -- flash-attention block grid ---------------------------------------
    kept, pruned = fa_block_candidates(seq, head_dim, blocks=blocks,
                                       budget=vmem_budget)
    report["fa"]["pruned"] = pruned
    _log(f"fa grid: {len(kept)} candidates, {len(pruned)} pruned "
         f"pre-compile (budget {vmem_budget >> 20} MiB)", log)
    program = f"flash_mha_s{seq}_d{head_dim}"
    # flash_mha's shard_map-aware out_shape needs jax.typeof (jax>=0.6);
    # without it the kernel cannot compile AT ALL in this host's jax —
    # same SKIP-not-PASS contract as tests/test_aot_tpu_compile.py: fall
    # back to the analytic touch model, recorded as such.
    fa_can_compile = hasattr(jax, "typeof")
    if kept and not fa_can_compile:
        _log("fa: jax.typeof unavailable — scoring the grid with the "
             "analytic touch model instead of compiled cost analysis "
             "(records tagged source=analytic)", log)
    for cand in kept:
        bq, bk = cand["fa_block_q"], cand["fa_block_k"]
        if fa_can_compile:
            try:
                compiled, desc = _fa_compile(topo.devices, seq, head_dim,
                                             heads, fa_batch, bq, bk)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                row = {"fa_block_q": bq, "fa_block_k": bk,
                       "error": f"{type(e).__name__}: {e}"[:300]}
                report["fa"]["compile_errors"].append(row)
                _log(f"  fa {bq}x{bk}: COMPILE ERROR {row['error'][:80]}",
                     log)
                continue
            pred = roofline.score_compiled(compiled, gen)
            pred["source"] = "compiled"
        else:
            flops, nbytes = fa_analytic_cost(seq, head_dim, heads,
                                             fa_batch, bq, bk)
            pred = roofline.score(gen, flops=flops, bytes_accessed=nbytes)
            pred["source"] = "analytic"
            desc = {"program": program,
                    "shape": [fa_batch, seq, heads, head_dim],
                    "causal": True, "block_q": bq, "block_k": bk}
        pred["vmem_bytes"] = cand["vmem_bytes"]
        db.add({"program": program, "family": "flash_attention",
                "fingerprint": tune_db.fingerprint(desc),
                "topology": topology, "generation": gen,
                "config": {"fa_block_q": bq, "fa_block_k": bk},
                "predicted": pred})
        row = dict(cand)
        row.update(predicted_ms=pred["predicted_ms"], bound=pred["bound"])
        report["fa"]["kept"].append(row)
        _log(f"  fa {bq}x{bk}: {pred['predicted_ms']} ms ({pred['bound']}-"
             f"bound, vmem {cand['vmem_bytes'] >> 10} KiB)", log)

    # -- bench ResNet-50 step x compiler-option sets x batch --------------
    for batch_per_chip in bench_batches:
        for name, opts in xla_opts_candidate_sets():
            try:
                compiled, desc = _bench_step_compile(
                    topo.devices, batch_per_chip, opts)
            except Exception as e:  # noqa: BLE001
                row = {"opts_name": name, "batch": batch_per_chip,
                       "error": f"{type(e).__name__}: {e}"[:300]}
                report["bench"]["compile_errors"].append(row)
                _log(f"  bench b{batch_per_chip} {name}: COMPILE ERROR "
                     f"{row['error'][:80]}", log)
                continue
            pred = roofline.score_compiled(compiled, gen)
            db.add({"program": desc["program"],
                    "family": "bench_resnet50",
                    "fingerprint": tune_db.fingerprint(desc, opts),
                    "topology": topology, "generation": gen,
                    "config": {"xla_opts": opts, "opts_name": name,
                               "batch": batch_per_chip},
                    "predicted": pred})
            row = {"opts_name": name, "batch": batch_per_chip,
                   "predicted_ms": pred["predicted_ms"],
                   "bound": pred["bound"], "fits": pred["fits"],
                   "gb": round(pred["bytes"] / 1e9, 1)}
            report["bench"]["rows"].append(row)
            _log(f"  bench b{batch_per_chip} {name}: "
                 f"{pred['predicted_ms']} ms ({pred['bound']}-bound, "
                 f"fits={pred['fits']})", log)

    # -- rank + persist ---------------------------------------------------
    report["fa"]["kept"].sort(key=lambda r: (r["predicted_ms"],
                                             -r["vmem_bytes"]))
    report["bench"]["rows"].sort(key=lambda r: r["predicted_ms"])
    report["ranked"] = {
        "flash_attention": [
            {"config": r.config, "predicted_ms":
             r.predicted.get("predicted_ms"),
             "vmem_bytes": r.predicted.get("vmem_bytes")}
            for r in db.top_k(5, family="flash_attention", generation=gen)],
        "bench_resnet50": [
            {"config": r.config, "predicted_ms":
             r.predicted.get("predicted_ms")}
            for r in db.top_k(5, family="bench_resnet50", generation=gen)],
    }
    db.save()
    _log(f"tuning DB: {db.path} ({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"tune_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


# ---------------------------------------------------------------------------
# Serving sweep: decode block sizes x slot counts for the serve_lm family.
# ---------------------------------------------------------------------------

def serve_bucket_sets(block: int, *, context_blocks: int = 4) -> tuple:
    """Prompt buckets derived from one decode block: powers of two up to
    the capacity (``context_blocks * block``) — the closed shape set the
    engine compiles for this block choice."""
    capacity = context_blocks * block
    buckets, b = [], block
    while b <= capacity:
        buckets.append(b)
        b *= 2
    return tuple(buckets), capacity


def _serve_decode_compile(topo_devices, cfg, slots: int, capacity: int):
    """AOT-compile the serving decode step (query length 1, donated KV)
    on ONE compile-only device — the exact program serve/engine.py
    builds, so the scored bytes are the served bytes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuframe.models.transformer_lm import TransformerLM
    from tpuframe.parallel import mesh as mesh_lib
    from tpuframe.serve import engine as engine_lib
    from tpuframe.serve import kv_cache as kv

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=1),
                              devices=list(topo_devices[:1]))
    repl = NamedSharding(mesh, P())
    model = TransformerLM(cfg)
    spec = kv.spec_for_model(cfg, slots=slots, capacity=capacity)
    decode_fn = engine_lib.make_decode_fn(model)

    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))

    def _sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl)

    p_sds = jax.tree.map(_sds, variables["params"])
    param_bytes = sum(
        int(_prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree_util.tree_leaves(variables["params"]))
    dtype = jnp.dtype(spec.dtype)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=repl)

    cache_sds = tuple((sds(spec.layer_shape(), dtype),
                       sds(spec.layer_shape(), dtype))
                      for _ in range(cfg.num_layers))
    compiled = jax.jit(decode_fn, donate_argnums=(1, 2, 3)).lower(
        p_sds, sds((slots, 1), jnp.int32), sds((slots,), jnp.int32),
        cache_sds).compile()
    desc = {"program": f"serve_decode_h{cfg.hidden_size}_"
                       f"l{cfg.num_layers}",
            "slots": slots, "capacity": capacity, "n_chips": 1,
            "dtype": cfg.dtype, "donate": True}
    return compiled, desc, param_bytes, spec


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def serve_sweep(topology: str = "v5e:2x2", *, db_path: str | None = None,
                report_path: str | None = None,
                blocks=(64, 128, 256), slots_grid=(8, 16),
                context_blocks: int = 4, log=None) -> dict:
    """Offline serving sweep: decode block sizes x slot counts for the
    ``serve_lm`` family, on a mid-size decoder (the smallest config
    where the params-vs-KV traffic split is representative).

    Objective is predicted ms PER TOKEN (step roofline / slots) — lower
    is better and ranks identically to tokens/sec/chip, but fits the
    DB's ``predicted_ms``-ascending ``_rank()`` contract directly.  Each
    row carries both the compiled ``cost_analysis`` roofline (when this
    jax can AOT-compile for the topology) and the analytic decode model
    (``roofline.decode_score``); compile failures degrade to the
    analytic row tagged ``source="analytic"`` — same SKIP-not-lie
    contract as the flash-attention grid above.
    """
    import jax  # noqa: F401 — fail fast before holding the lock
    from jax.experimental import topologies

    from tpuframe.models.transformer_lm import LMConfig
    from tpuframe.serve import kv_cache as kv_lib

    hold_aot_lock()
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    gen = roofline.generation_from_topology(topology)
    topo = topologies.get_topology_desc(topology, platform="tpu")
    _log(f"serve sweep on {topology}: blocks {tuple(blocks)} x slots "
         f"{tuple(slots_grid)}", log)

    cfg = LMConfig(vocab_size=8192, hidden_size=512, num_layers=4,
                   num_heads=8, intermediate_size=2048,
                   max_seq=context_blocks * max(blocks),
                   dtype="bfloat16", attn_impl="xla")
    program = f"serve_decode_h{cfg.hidden_size}_l{cfg.num_layers}"

    db_path = db_path or tune_db.default_db_path()
    db = tune_db.TuningDB.open(db_path) if os.path.exists(db_path) \
        else tune_db.TuningDB(db_path)
    report = {"topology": topology, "generation": gen, "program": program,
              "objective": "predicted_ms_per_token",
              "model": {"hidden": cfg.hidden_size,
                        "layers": cfg.num_layers, "heads": cfg.num_heads,
                        "dtype": cfg.dtype},
              "serve": {"rows": [], "compile_errors": []}}

    for block in blocks:
        buckets, capacity = serve_bucket_sets(
            block, context_blocks=context_blocks)
        for slots in slots_grid:
            spec = kv_lib.spec_for_model(cfg, slots=slots,
                                         capacity=capacity)
            analytic = roofline.decode_score(
                param_bytes=_model_param_bytes(cfg),
                kv_bytes_per_token=spec.bytes_per_token(),
                slots=slots, context=capacity, generation=gen,
                param_dtype_bytes=2)
            pred = None
            try:
                compiled, desc, pb, _ = _serve_decode_compile(
                    topo.devices, cfg, slots, capacity)
                pred = roofline.score_compiled(compiled, gen)
                pred["source"] = "compiled"
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                err = f"{type(e).__name__}: {e}"[:300]
                report["serve"]["compile_errors"].append(
                    {"decode_block": block, "slots": slots, "error": err})
                _log(f"  serve block={block} slots={slots}: COMPILE "
                     f"FALLBACK {err[:80]}", log)
                desc = {"program": program, "slots": slots,
                        "capacity": capacity, "dtype": cfg.dtype}
                pred = roofline.score(
                    gen, flops=analytic.flops_per_step,
                    bytes_accessed=analytic.bytes_per_step)
                pred["source"] = "analytic"
            # Per-token objective + the throughput bound the report and
            # obs comparisons use.
            pred["predicted_ms"] = round(pred["predicted_ms"]
                                         / max(slots, 1), 4)
            pred["tokens_per_s_per_chip"] = round(
                slots / (pred["predicted_ms"] * 1e-3 * slots), 2) \
                if pred["predicted_ms"] > 0 else None
            pred["analytic_tokens_per_s_per_chip"] = \
                analytic.tokens_per_s_per_chip
            config = {"decode_block": int(block),
                      "prompt_buckets": [int(b) for b in buckets],
                      "slots": int(slots)}
            db.add({"program": program, "family": "serve_lm",
                    "fingerprint": tune_db.fingerprint(desc),
                    "topology": topology, "generation": gen,
                    "config": config, "predicted": pred})
            row = dict(config)
            row.update(capacity=capacity, source=pred["source"],
                       predicted_ms_per_token=pred["predicted_ms"],
                       bound=pred["bound"],
                       tokens_per_s_per_chip=pred["tokens_per_s_per_chip"],
                       analytic_tokens_per_s_per_chip=(
                           analytic.tokens_per_s_per_chip))
            report["serve"]["rows"].append(row)
            _log(f"  serve block={block} slots={slots}: "
                 f"{pred['predicted_ms']} ms/token "
                 f"({pred['bound']}-bound, "
                 f"{pred['tokens_per_s_per_chip']} tok/s/chip, "
                 f"{pred['source']})", log)

    report["serve"]["rows"].sort(key=lambda r: r["predicted_ms_per_token"])
    report["winner"] = (report["serve"]["rows"][0]
                        if report["serve"]["rows"] else None)
    report["ranked"] = [
        {"config": r.config,
         "predicted_ms_per_token": r.predicted.get("predicted_ms"),
         "source": r.predicted.get("source")}
        for r in db.top_k(5, family="serve_lm", generation=gen)]
    db.save()
    _log(f"tuning DB: {db.path} ({len(db.data['records'])} records)", log)
    if report_path is None:
        tag = topology.replace(":", "_").replace("x", "")
        report_path = os.path.join(tune_db.repo_root(), "perf", "results",
                                   f"serve_report_{tag}.json")
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    _log(f"report: {report_path}", log)
    return report


def _model_param_bytes(cfg) -> int:
    """Parameter bytes of a TransformerLM without building arrays."""
    import jax
    import jax.numpy as jnp

    from tpuframe.models.transformer_lm import TransformerLM

    variables = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
    return sum(int(_prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in jax.tree_util.tree_leaves(variables["params"]))
