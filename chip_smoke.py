"""chip_smoke.py — the quickest proof that tpuframe still starts on the chip.

One process drives the system's main path once on ONE TPU chip, through the
entry points a user calls, at the published width and depth of the models
the repo ships, with random weights made from a seed:

  1. device    — what jax found; must be a TPU
  2. resnet50  — ``tpuframe.train.main`` on ``imagenet_resnet50``, synthetic
                 224x224, bf16, global batch 256: a few steps, one eval, a
                 checkpoint written, then a second run that resumes from it
  3. lm124m    — ``tpuframe.train.main`` on ``lm_long`` cut to one chip and
                 2048 tokens by ``--set``: the default 124M LM with the
                 Pallas flash kernel and the fused cross-entropy, b8 x 2048;
                 the same seed with XLA attention; flash fwd+bwd against
                 ``ops/attention.py``'s XLA path at the step's own shape
  4. server    — ``python -m tpuframe.serve --model lm-124m``'s code path:
                 LMEngine behind the scheduler and the seeded load
                 generator, then prefill+decode against the training
                 forward (golden parity) at the same width

Each phase prints one JSON line as it ends, with the compile-cache hits and
misses it cost.  Any failed check raises: the exit code is non-zero and the
last line is not the success object.  Step times are printed as
observations of a smoke run, never as benchmark results.

``--chips 4`` runs one thing instead: the phase-3 LM step over a ``data=4``
mesh and the same seed and global batch on one of the four chips.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # four chips, dp=4 against one chip

Exits non-zero at once when jax finds no TPU.  Last line on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "smoke")

# Stated tolerances (bf16 compute: 2^-8 relative per rounded activation).
# Beside each, the largest value seen on a v5e in PR 21 (PERF.md Findings).
LOSS_TOL_ATTN = 0.01    # |loss_pallas - loss_xla| at step 1, loss ~10.9: 2e-4
LOSS_TOL_DP = 0.01      # |loss_dp4 - loss_1chip| at every step
FLASH_REL_TOL = 0.03    # ||flash - xla||_F / ||xla||_F, out and grads: 0.019
PARITY_ATOL = 0.15      # max |logit diff| prefill+decode vs forward: 0.051

RESNET_SETS = (
    "global_batch=256", "log_every=1", "eval_every=8", "eval_batches=1",
    "ckpt_every=4",
    # the eval split is synthetic_size // 8 images: one batch of 256
    'dataset_kwargs={"synthetic_size": 2048}',
)
RESNET_STEPS, RESNET_RESUME_STEPS = 8, 10

# lm_long is ring attention over a data x seq mesh at 32k; --set cuts it to
# plain data parallelism at 2048 tokens.  warmup_steps=0 so the steps after
# the first move the loss and a wrong gradient would show.
LM_SETS = (
    "global_batch=8", "total_steps=4", "log_every=1", "eval_every=4",
    "eval_batches=1", "warmup_steps=0", "shard_seq=False",
    'mesh={"data": -1}',
    'model_kwargs={"seq_mode": None, "max_seq": 2048}',
    'dataset_kwargs={"seq_len": 2048}',
)
FLASH_SHAPE = (8, 2048, 12, 64)

SERVE_ARGS = ("--model", "lm-124m", "--requests", "12", "--steps", "4000",
              "--slots", "4", "--max-new-tokens", "8")
PARITY_BUCKETS = (128, 256)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> dict:
    """One JSON line per phase, on stdout and kept in chiprun_out/."""
    line = {"phase": phase, **fields}
    text = json.dumps(line, default=str)
    print(text, flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "phases.jsonl"), "a") as f:
        f.write(text + "\n")
    return line


def _cache_counts() -> dict:
    from tpuframe.obs import metrics

    c = metrics.counters("compile_cache.")
    return {"hits": int(c.get("compile_cache.hits", 0)),
            "misses": int(c.get("compile_cache.misses", 0))}


def _cache_delta(before: dict) -> dict:
    now = _cache_counts()
    return {k: now[k] - before[k] for k in now}


def _free_device_memory() -> dict:
    """Drop what the last phase left on the device (16 GB of HBM holds one
    phase at a time) and report what is still in use."""
    import jax

    jax.clear_caches()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    return {"bytes_in_use_after_free": stats.get("bytes_in_use")}


def _fresh_dir(*parts: str) -> str:
    path = os.path.join(OUT_DIR, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _set_args(sets) -> list[str]:
    return [a for s in sets for a in ("--set", s)]


def _events(events_dir: str) -> list[dict]:
    from tpuframe.obs import events

    return events.merge(events_dir)


def _step_report(records: list[dict]) -> dict:
    """Losses and host step times from one run's ``step`` events.  With
    log_every=1 every record follows a loss fetch, so the gap between two
    records is a whole step on the host's clock."""
    steps = [r for r in records if r.get("type") == "step"]
    losses = [r.get("loss") for r in steps]
    check(bool(steps) and all(
        isinstance(x, float) and math.isfinite(x) for x in losses),
        f"a step has no finite loss: {losses}")
    gaps = [round(1e3 * (b["t"] - a["t"]), 1)
            for a, b in zip(steps, steps[1:])]
    return {"steps": [r["step"] for r in steps],
            "losses": [round(x, 5) for x in losses],
            "first_step_ms_with_compile": steps[0]["wall_ms"],
            "step_ms_smoke_observation_not_a_benchmark": gaps}


def _run_trainer(config: str, sets, events_dir: str, *,
                 ckpt_dir: str | None = None) -> tuple[dict, list[dict]]:
    """``python -m tpuframe.train``'s own ``main``, in this process."""
    from tpuframe import train

    argv = ["--config", config, *_set_args(sets), "--events-dir", events_dir]
    if ckpt_dir:
        argv += ["--ckpt-dir", ckpt_dir]
    metrics = train.main(argv)
    records = _events(events_dir)
    start = next(r for r in records if r["type"] == "run_start")
    check(start.get("generation_source") != "assumed",
          f"run_start prices MFU at an assumed generation: {start}")
    return metrics, records


def _kernel_impls(records: list[dict]) -> dict:
    out: dict = {}
    for r in records:
        if r.get("type") == "kernel_impl":
            out.setdefault(r["op"], {})[r["impl"]] = r["why"]
    return out


# ---------------------------------------------------------------------------
# Phase 1 — device
# ---------------------------------------------------------------------------

def phase_device(*, require_tpu: bool = True, chips: int = 1) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform {dev.platform!r}, "
              f"{len(devices)} device(s)); nothing to smoke",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    check(len(devices) == chips,
          f"asked for {chips} chip(s), jax has {len(devices)}")

    from tpuframe import native
    from tpuframe.tune import roofline
    from tpuframe.utils import compile_cache

    generation, gen_source = roofline.device_generation(dev)
    check(not require_tpu or gen_source != "assumed",
          f"generation {generation} is assumed, not read from the device")
    # The pure-Python crc32c fallback would take hours on a real checkpoint.
    check(native.available(),
          f"native library did not build: {native.load_error()}")
    cache_dir, cache_source = compile_cache.location()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), generation=generation,
         generation_source=gen_source, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         compile_cache_dir=cache_dir, compile_cache_source=cache_source,
         compile_cache_entries_at_start=(
             len(os.listdir(cache_dir))
             if cache_dir and os.path.isdir(cache_dir) else 0),
         native_available=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# Phase 2 — trainer, ResNet-50
# ---------------------------------------------------------------------------

def phase_resnet(*, config: str = "imagenet_resnet50", sets=RESNET_SETS,
                 total: int = RESNET_STEPS,
                 resume_steps: int = RESNET_RESUME_STEPS) -> dict:
    from tpuframe import ckpt as ckpt_lib

    before = _cache_counts()
    ckpt_dir = _fresh_dir("resnet50", "ckpt")

    t0 = time.time()
    metrics, records = _run_trainer(
        config, [*sets, f"total_steps={total}"],
        _fresh_dir("resnet50", "events"), ckpt_dir=ckpt_dir)
    report = _step_report(records)
    check(report["steps"] == list(range(1, total + 1)),
          f"expected steps 1..{total}, got {report['steps']}")
    check(math.isfinite(metrics.get("eval_loss", float("nan"))),
          f"no finite eval loss: {metrics}")
    saved = [r["step"] for r in records if r["type"] == "ckpt_save"]
    check(ckpt_lib.latest_step(ckpt_dir) == total and total in saved,
          f"no committed checkpoint at step {total}: saved {saved}")
    first_run_s = round(time.time() - t0, 1)

    # Read it back the way a user does: run again, resume, go on.
    first_cache = _cache_delta(before)
    _, records2 = _run_trainer(
        config, [*sets, f"total_steps={resume_steps}"],
        _fresh_dir("resnet50", "events_resume"), ckpt_dir=ckpt_dir)
    restored = [r["step"] for r in records2 if r["type"] == "ckpt_restore"]
    report2 = _step_report(records2)
    check(restored == [total], f"resume restored {restored}, not [{total}]")
    check(report2["steps"] == list(range(total + 1, resume_steps + 1)),
          f"resume ran steps {report2['steps']}")
    shutil.rmtree(ckpt_dir)  # ~0.6 GB that chiprun_out/ need not bring back
    return emit(
        "resnet50", config=config, sets=list(sets), total_steps=total,
        first_run_s=first_run_s,
        **report, eval_loss=round(metrics["eval_loss"], 5),
        ckpt_saved_steps=saved, resumed_from=restored[0],
        resume_steps=report2["steps"], resume_losses=report2["losses"],
        compile_cache_first_run=first_cache,
        compile_cache=_cache_delta(before), **_free_device_memory())


# ---------------------------------------------------------------------------
# Phase 3 — trainer, 124M LM with the kernels
# ---------------------------------------------------------------------------

def _compiled_step_text(config: str, sets):
    """The compiled text of the step the trainer runs for this config — the
    same harness and the same jit, compiled once more (a cache hit).
    Returns ``(text, harness, batch)``; the harness holds the state on the
    device for as long as the caller keeps it."""
    from tpuframe import train
    from tpuframe.utils import get_config

    cfg = get_config(config).with_overrides(**train._parse_set(list(sets)))
    h = train.build_harness(cfg)
    batch = next(iter(h.train_loader))
    text = h.train_step.lower(h.state, batch).compile().as_text()
    h.train_loader.close()
    h.eval_loader.close()
    return text, h, batch


def flash_vs_xla(shape=FLASH_SHAPE, *, seed: int = 0,
                 rel_tol: float = FLASH_REL_TOL) -> dict:
    """Flash fwd+bwd against ops/attention.py's XLA path, causal bf16, as
    tests/test_flash_attention_tpu.py does at toy size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuframe.ops import attention as attn_ops

    keys = jax.random.split(jax.random.key(seed), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
               * 0.5 for kk in keys)

    def make(impl):
        def loss(q, k, v):
            out = attn_ops.multihead_attention(q, k, v, causal=True,
                                               impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out_f), g_f = make("pallas")(q, k, v)
    (_, out_x), g_x = make("xla")(q, k, v)
    errs = {}
    for name, a, b in (("out", out_f, out_x), ("dq", g_f[0], g_x[0]),
                       ("dk", g_f[1], g_x[1]), ("dv", g_f[2], g_x[2])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(bool(np.isfinite(a).all()), f"flash {name} is not finite")
        errs[name] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        check(errs[name] <= rel_tol,
              f"flash {name} differs from XLA attention by "
              f"{errs[name]:.3e} (relative Frobenius) > {rel_tol}")
    return {"shape": list(shape), "rel_tol": rel_tol,
            "rel_err": {k: round(v, 6) for k, v in errs.items()}}


def phase_lm(*, config: str = "lm_long", sets=LM_SETS,
             flash_shape=FLASH_SHAPE, want_impl: str = "mosaic") -> dict:
    before = _cache_counts()
    t0 = time.time()
    _, records = _run_trainer(config, sets, _fresh_dir("lm124m", "events"))
    report = _step_report(records)
    impls = _kernel_impls(records)
    check(impls.get("flash_attention") is not None
          and set(impls["flash_attention"]) == {want_impl},
          f"the step asked for the flash kernel and resolved to {impls}, "
          f"not {want_impl} only")
    # train.main returned after its last loss fetch and eval: synced.
    pallas_s = round(time.time() - t0, 1)  # tf-lint: ok[TF103]

    n_kernels = _compiled_step_text(config, sets)[0].count("tpu_custom_call")
    if want_impl == "mosaic":
        check(n_kernels > 0,
              "no Mosaic custom call (tpu_custom_call) in the compiled step")
    freed = _free_device_memory()

    # a repeated --set model_kwargs= merges into the first (train._parse_set)
    xla_sets = [*sets, 'model_kwargs={"attn_impl": "xla"}']
    _, xla_records = _run_trainer(config, xla_sets,
                                  _fresh_dir("lm124m", "events_xla"))
    xla_report = _step_report(xla_records)
    check(not _kernel_impls(xla_records),
          f"the XLA-attention run used a kernel: "
          f"{_kernel_impls(xla_records)}")
    diffs = [round(abs(a - b), 5) for a, b in
             zip(report["losses"], xla_report["losses"])]
    check(diffs[0] <= LOSS_TOL_ATTN,
          f"step-1 loss: pallas {report['losses'][0]} vs xla "
          f"{xla_report['losses'][0]} differ by more than {LOSS_TOL_ATTN}")
    _free_device_memory()

    flash = flash_vs_xla(flash_shape)
    return emit(
        "lm124m", config=config, sets=list(sets), pallas_run_s=pallas_s,
        **report, kernel_impl=impls, mosaic_custom_calls_in_step=n_kernels,
        xla_attention_losses=xla_report["losses"],
        xla_attention_step_ms_smoke_observation_not_a_benchmark=xla_report[
            "step_ms_smoke_observation_not_a_benchmark"],
        abs_loss_diff_vs_xla=diffs, loss_tol_step1=LOSS_TOL_ATTN,
        flash_vs_xla=flash, compile_cache=_cache_delta(before),
        bytes_in_use_after_pallas_run=freed["bytes_in_use_after_free"],
        **_free_device_memory())


# ---------------------------------------------------------------------------
# Phase 4 — server
# ---------------------------------------------------------------------------

def phase_server(*, serve_args=SERVE_ARGS, parity_buckets=PARITY_BUCKETS,
                 parity_atol: float = PARITY_ATOL, seed: int = 0) -> dict:
    from tpuframe.serve import __main__ as serve_cli
    from tpuframe.serve import kv_cache as kv
    from tpuframe.serve.engine import golden_parity_diffs

    before = _cache_counts()
    args = serve_cli.parse_args([*serve_args, "--seed", str(seed),
                                 "--events-dir",
                                 _fresh_dir("server", "events")])
    t0 = time.time()
    stats = serve_cli.run(args)
    n = args.requests
    check(stats["submitted"] == n and stats["requests"] == n
          and stats["unfinished"] == 0,
          f"sent {n} requests: {stats}")
    served = [r for r in _events(args.events_dir)
              if r["type"] == "serve_request"]
    check(sorted(r["id"] for r in served) == list(range(n)),
          f"a request was lost: served ids {[r['id'] for r in served]}")
    check(all(r["output_tokens"] == args.max_new_tokens for r in served),
          "a request finished short of max_new_tokens")
    buckets_hit = sorted({kv.bucket_for(r["prompt_tokens"],
                                        kv.resolve_buckets())
                          for r in served})
    check(len(buckets_hit) >= 2, f"traffic hit buckets {buckets_hit} only")
    serve_s = round(time.time() - t0, 1)
    _free_device_memory()

    decode_tokens = 4
    cfg = serve_cli.model_config(args.model)
    rows = golden_parity_diffs(
        cfg, buckets=parity_buckets, decode_tokens=decode_tokens, seed=seed,
        capacity=kv.capacity_for(max(parity_buckets) + decode_tokens,
                                 kv.DEFAULT_DECODE_BLOCK))
    for bucket, prompt_len, diff in rows:
        check(diff is not None and diff <= parity_atol,
              f"golden parity, bucket {bucket} prompt {prompt_len}: max "
              f"|logit diff| {diff} > {parity_atol}")
    ttft = sorted(r["ttft_ms"] for r in served)
    return emit(
        "server", model=args.model, dtype=cfg.dtype, requests=n,
        finished=stats["requests"], lost=0, prompt_buckets_hit=buckets_hit,
        scheduler_steps=stats["steps"], total_tokens=stats["total_tokens"],
        serve_s_with_compile=serve_s,
        smoke_observation_not_a_benchmark={
            "tokens_per_s": stats["tokens_per_s"],
            "ttft_ms_median": ttft[len(ttft) // 2]},
        golden_parity_max_abs_logit_diff=[
            {"bucket": b, "prompt_len": p, "diff": round(d, 5)}
            for b, p, d in rows],
        parity_atol=parity_atol, compile_cache=_cache_delta(before),
        **_free_device_memory())


# ---------------------------------------------------------------------------
# --chips 4 — the LM step over data=4 against one of the four chips
# ---------------------------------------------------------------------------

_GROUP_LIST = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUP_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def all_reduce_group_sizes(hlo_text: str) -> list[int]:
    """Members of the first replica group of every all-reduce in compiled
    HLO text, in either of XLA's spellings (``{{0,1,2,3}}`` or the iota
    form ``[1,4]<=[4]``: groups x members)."""
    sizes = []
    for line in hlo_text.splitlines():
        if " all-reduce(" not in line and " all-reduce-start(" not in line:
            continue
        m = _GROUP_LIST.search(line)
        if m:
            sizes.append(len(m.group(1).split(",")))
            continue
        m = _GROUP_IOTA.search(line)
        if m:
            sizes.append(int(m.group(2)))
    return sizes


def phase_dp(*, config: str = "lm_long", sets=LM_SETS, n: int = 4,
             want_impl: str = "mosaic") -> dict:
    import jax

    before = _cache_counts()
    _, records = _run_trainer(config, sets, _fresh_dir("dp", "events"))
    report = _step_report(records)
    start = next(r for r in records if r["type"] == "run_start")
    check(start["devices"] == n and (start.get("mesh") or {}).get("data") == n,
          f"the run was not data={n}: {start}")
    impls = _kernel_impls(records)
    check(set(impls.get("flash_attention", {})) == {want_impl},
          f"flash kernel resolved to {impls}, not {want_impl} only")

    # The same harness once more, held while we look at it: where the batch
    # and the state live, and what the compiled step says.
    text, h, batch = _compiled_step_text(config, sets)
    ids = batch["input_ids"]
    shard_devs = sorted(s.device.id for s in ids.addressable_shards)
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in ids.addressable_shards})
    check(shard_devs == sorted(d.id for d in jax.devices())
          and shard_shapes == [(ids.shape[0] // n, ids.shape[1])],
          f"batch shards on devices {shard_devs} with shapes {shard_shapes}")
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()}
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(h.state.params))
    if jax.devices()[0].platform == "tpu":
        check(all(b is not None and b >= param_bytes
                  for b in in_use.values()),
              f"a device holds less than one copy of the params "
              f"({param_bytes} B): {in_use}")
    groups = all_reduce_group_sizes(text)
    check(n in groups, f"no all-reduce over {n} members in the compiled "
                       f"step: group sizes {groups}")
    del h, batch
    _free_device_memory()

    one_sets = [*sets, "distributed=False"]
    _, one_records = _run_trainer(config, one_sets,
                                  _fresh_dir("dp", "events_one_chip"))
    one_report = _step_report(one_records)
    one_start = next(r for r in one_records if r["type"] == "run_start")
    check(one_start.get("mesh") is None,
          f"the comparison run built a mesh: {one_start}")
    diffs = [round(abs(a - b), 5) for a, b in
             zip(report["losses"], one_report["losses"])]
    check(len(diffs) == len(report["losses"])
          and max(diffs) <= LOSS_TOL_DP,
          f"dp={n} losses {report['losses']} vs one chip "
          f"{one_report['losses']}: differ by more than {LOSS_TOL_DP}")
    return emit(
        f"dp{n}", config=config, sets=list(sets), **report,
        one_chip_losses=one_report["losses"],
        one_chip_step_ms_smoke_observation_not_a_benchmark=one_report[
            "step_ms_smoke_observation_not_a_benchmark"],
        abs_loss_diff=diffs, loss_tol=LOSS_TOL_DP, kernel_impl=impls,
        batch_shard_devices=shard_devs, batch_shard_shape=shard_shapes[0],
        bytes_in_use_per_device=in_use, param_bytes=param_bytes,
        all_reduce_group_sizes=sorted(set(groups)),
        compile_cache=_cache_delta(before), **_free_device_memory())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp=4 LM step against one chip")
    args = ap.parse_args(argv)

    device = phase_device(chips=args.chips)
    if args.chips == 4:
        phase_dp(n=4)
    else:
        phase_resnet()
        phase_lm()
        phase_server()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
