"""``python -m tpuframe.serve`` — serving loadgen CLI + CPU selfcheck.

Default mode runs the open-loop load generator over a named model with
continuous batching and prints the summary stats (writing obs v2 events
when ``TPUFRAME_EVENTS_DIR``/``--events-dir`` is set)::

    python -m tpuframe.serve --model tiny-lm --steps 100
    python -m tpuframe.serve --model lm-124m --requests 12 --steps 2000

``--selfcheck`` is the CI/acceptance entry: golden-logits parity on
every bucket, a full loadgen run with events, an ``obs summarize``
subprocess proving the TTFT/TPOT/tokens-per-sec reporting path, a BERT
single-shot classification smoke and a fleet smoke — all on CPU, no
accelerator required.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile


def model_config(model: str):
    """``--model`` name -> ``LMConfig``: the toy, or the default 124M LM
    (models/transformer_lm.py) in bf16 — the width the trainer's LM
    configs run at."""
    from tpuframe.models.transformer_lm import LMConfig

    models = {"tiny-lm": LMConfig.tiny,
              "lm-124m": lambda: LMConfig(dtype="bfloat16")}
    if model not in models:
        raise SystemExit(f"unknown --model {model!r} "
                         f"(have: {', '.join(sorted(models))})")
    return models[model]()


def _build_engine(model: str, *, slots: int, buckets, decode_block,
                  max_context):
    from tpuframe.serve.engine import LMEngine

    return LMEngine(model_config(model), slots=slots,
                    prompt_buckets=buckets, decode_block=decode_block,
                    max_context=max_context)


def run(args) -> dict:
    """Build the engine for ``args.model`` and drive the seeded loadgen
    through the scheduler; returns ``run_loadgen``'s stats."""
    from tpuframe.obs import events as obs_events
    from tpuframe.serve import loadgen

    if args.events_dir:
        os.environ["TPUFRAME_EVENTS_DIR"] = args.events_dir
    obs_events.init()

    print(f"[serve] building engine for {args.model} "
          f"(slots={args.slots}) ...", flush=True)
    engine = _build_engine(args.model, slots=args.slots, buckets=None,
                           decode_block=None, max_context=None)
    n_requests = args.requests or max(1, args.steps // 4)
    reqs = loadgen.synthetic_requests(
        n_requests, buckets=engine.prompt_buckets,
        vocab_size=engine.cfg.vocab_size, seed=args.seed,
        max_new_tokens=args.max_new_tokens)
    stats = loadgen.run_loadgen(engine, reqs, max_steps=args.steps,
                                log=lambda m: print(f"[serve] {m}"))
    for key in ("requests", "steps", "total_tokens", "tokens_per_s",
                "tokens_per_s_per_chip"):
        print(f"[serve] {key}: {stats[key]}")
    if stats["unfinished"]:
        print(f"[serve] {stats['unfinished']} request(s) still in flight "
              f"at the --steps cap")
    obs_events.close()
    return stats


def cmd_run(args) -> int:
    # The step cap bounds the run, not its correctness — fail only when
    # the engine served nothing at all.
    return 0 if run(args)["requests"] > 0 else 1


def cmd_selfcheck(args) -> int:
    from tpuframe.models.bert import BertConfig
    from tpuframe.models.transformer_lm import LMConfig
    from tpuframe.obs import events as obs_events
    from tpuframe.serve import kv_cache as kv
    from tpuframe.serve import loadgen
    from tpuframe.serve.engine import (BertClassifier, LMEngine,
                                       golden_parity_check)

    failures = []
    buckets = (16, 32)
    block = 16
    decode_tokens = 4
    cfg = LMConfig.tiny()

    # 1. Golden-logits parity: prefill+decode == training forward, every
    #    bucket, full and ragged prompt lengths.  Capacity leaves head
    #    room for the decoded tail on top of the largest bucket.
    cap = kv.capacity_for(max(buckets) + decode_tokens, block)
    problems = golden_parity_check(cfg, buckets=buckets, capacity=cap,
                                   decode_tokens=decode_tokens)
    for p in problems:
        failures.append(f"parity: {p}")
    print(f"[serve] parity: {len(buckets)} buckets, "
          f"{len(problems)} problem(s)")

    # 2. Continuous-batching loadgen with obs events on.
    with tempfile.TemporaryDirectory(prefix="tpuframe-serve-") as tmp:
        events_dir = os.path.join(tmp, "events")
        obs_events.init(events_dir)
        engine = LMEngine(cfg, slots=3, prompt_buckets=buckets,
                          decode_block=block,
                          max_context=max(buckets) + decode_tokens)
        reqs = loadgen.synthetic_requests(
            8, buckets=buckets, vocab_size=cfg.vocab_size,
            max_new_tokens=decode_tokens, seed=args.seed)
        stats = loadgen.run_loadgen(engine, reqs)
        obs_events.close()
        if stats["requests"] != 8 or stats["unfinished"]:
            failures.append(f"loadgen: {stats['requests']}/8 requests "
                            f"completed, {stats['unfinished']} unfinished")
        print(f"[serve] loadgen: {stats['requests']} requests, "
              f"{stats['total_tokens']} tokens, "
              f"{stats['tokens_per_s']} tok/s")

        # 3. The offline analyzer reports serving latency from those
        #    events (TTFT/TPOT percentiles, tokens/sec/chip).
        proc = subprocess.run(
            [sys.executable, "-m", "tpuframe.obs", "summarize",
             events_dir],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        out = proc.stdout
        if proc.returncode != 0:
            failures.append(f"obs summarize exited {proc.returncode}: "
                            f"{proc.stderr.strip()[-200:]}")
        for needle in ("serving", "TTFT", "TPOT", "tokens/s"):
            if needle not in out:
                failures.append(f"obs summarize missing {needle!r} "
                                "in serve section")
        print("[serve] obs summarize serve section:")
        for line in out.splitlines():
            if any(k in line for k in ("serving", "TTFT", "TPOT",
                                       "tokens/s")):
                print(f"    {line.strip()}")

    # 4. Single-shot BERT classification (the non-autoregressive path).
    clf = BertClassifier(BertConfig.tiny(num_classes=3), buckets=(16, 32))
    label, probs = clf.classify(list(range(1, 11)))
    if not (0 <= label < 3 and abs(float(probs.sum()) - 1.0) < 1e-4):
        failures.append(f"bert classify: label={label} "
                        f"probs_sum={float(probs.sum()):.4f}")
    print(f"[serve] bert classify: label={label} ok")

    # 5. Fleet smoke: 2 fake-engine replica subprocesses behind the
    #    router, seeded loadgen, one replica_crash mid-run — the
    #    zero-loss drain/redispatch contract on every CI run (the full
    #    3-replica latency proof lives in tests/test_chaos.py).
    from tpuframe.serve import router as router_lib

    try:
        fleet = router_lib.fleet_smoke(
            replicas=2, n_requests=10, kill_rank=1, kill_step=3,
            step_delay_ms=5.0, seed=args.seed,
            log=lambda m: print(f"[serve] {m}"))
    except Exception as e:  # noqa: BLE001 — a harness crash is a failure
        failures.append(f"fleet smoke crashed: {type(e).__name__}: {e}")
    else:
        if fleet["lost"] or fleet["shed"] or fleet["timed_out"]:
            failures.append(
                f"fleet smoke: lost={fleet['lost']} shed={fleet['shed']} "
                f"timed_out={fleet['timed_out']} (want 0/0/False)")
        if fleet["drains"] < 1:
            failures.append("fleet smoke: replica_crash produced no "
                            "router drain")
        print(f"[serve] fleet smoke: {fleet['requests']} requests, "
              f"{fleet['drains']} drain(s), "
              f"{fleet['redispatched']} redispatched, "
              f"exit codes {fleet['exit_codes']}")

    for f in failures:
        print(f"SERVE FAIL {f}")
    print(f"[serve] selfcheck: {len(failures)} failure(s)")
    return 1 if failures else 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m tpuframe.serve",
        description="tpuframe serving loadgen / selfcheck")
    ap.add_argument("--model", default="tiny-lm",
                    help="tiny-lm | lm-124m")
    ap.add_argument("--steps", type=int, default=100,
                    help="max scheduler steps for the loadgen run")
    ap.add_argument("--requests", type=int, default=None,
                    help="synthetic requests to send (default steps/4)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events-dir", default=None,
                    help="write obs v2 events here (else "
                         "TPUFRAME_EVENTS_DIR)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="run the CPU acceptance selfcheck and exit")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.selfcheck:
        return cmd_selfcheck(args)
    return cmd_run(args)


if __name__ == "__main__":
    raise SystemExit(main())
