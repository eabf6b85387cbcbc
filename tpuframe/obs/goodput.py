"""Goodput & MFU accounting — "fast as the hardware allows", verified.

The MLPerf TPU-pod scaling work (arXiv:1909.09756) reports MFU/step-time
accounting as the north-star efficiency metric; ROADMAP's claim is
unverifiable without it.  This module splits a run's wall clock into
named buckets and turns step time into an MFU estimate against the
roofline hardware tables (``tune/roofline.py`` — the same peaks every
PERF.md roofline and bench.py's MFU column use, so the three can never
disagree).

Buckets (seconds; they partition attempt wall time):

  init        process start → first step dispatched (harness build,
              data/restore — includes ckpt_restore time)
  compile     the first train step's wall time (XLA compile + one step;
              host-side the two are indistinguishable, and the compile
              dominates by orders of magnitude)
  productive  steps 2..N — the only bucket that moves the loss
  input       time the train loop sat blocked on the data pipeline
              (``next(data_iter)`` / the prefetch queue's ``q.get()``) —
              the MLPerf-pod scaling work's "input stall" number
              (arXiv:1909.09756), split out of step time in schema v2
  ckpt        blocking checkpoint time (async saves cost only their
              snapshot slice)
  eval        eval passes (incl. the eval program's first compile)
  stall       watchdog-detected dead time (heartbeat ``stall`` events)
  other       wall − sum(above): logging, GC, supervisor glue

Restart-lost time is a *cross-attempt* fact: the analyzer computes it
when stitching attempts — (crashed attempt's time past its last
committed step) + (gap until the relaunch's first event).  A single
attempt cannot know it died.

Two MFU flavors are reported: ``mfu_productive`` (model flops / peak,
over productive step time — the kernel-efficiency number) and
``mfu_goodput`` (over total wall — the fleet-efficiency number; the gap
between the two is exactly the non-productive buckets).

Pure stdlib + ``tune.roofline`` (itself stdlib); both the live meter in
train.py and the offline analyzer share these definitions, so the
run_end summary and ``python -m tpuframe.obs summarize`` can never
drift apart.
"""

from __future__ import annotations

import time

from tpuframe.tune import roofline

BUCKETS = ("init", "compile", "productive", "input", "ckpt", "eval",
           "stall", "other")


class GoodputMeter:
    """Live bucket accounting for one attempt (train.py's half).

    The loop charges named buckets as it goes; ``summary()`` closes the
    books — ``other`` absorbs the unattributed remainder so the buckets
    always sum to wall time exactly (the analyzer asserts this).
    ``clock`` is injectable for the fake-clock tests.
    """

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._buckets = {b: 0.0 for b in BUCKETS if b != "other"}
        self.steps = 0
        self.first_step_s: float | None = None

    def charge(self, bucket: str, seconds: float) -> None:
        if bucket not in self._buckets:
            raise ValueError(f"unknown goodput bucket {bucket!r}; "
                             f"have {sorted(self._buckets)}")
        self._buckets[bucket] += max(0.0, seconds)

    def step(self, seconds: float) -> None:
        """Charge one training step.  The first step is the compile."""
        if self.first_step_s is None:
            self.first_step_s = seconds
            self.charge("compile", seconds)
        else:
            self.charge("productive", seconds)
        self.steps += 1

    def wall_s(self) -> float:
        return self._clock() - self._t0

    def unaccounted_s(self) -> float:
        """Wall time not yet charged to any bucket — what ``other`` would
        absorb right now.  The stall-abort path charges ``min(idle,
        unaccounted_s())``: the watchdog's idle window can overlap a step
        that completed without beating (the injected-hang seam sits
        between the charge and the beat), and the cap keeps the buckets
        from summing past wall."""
        return max(0.0, self.wall_s() - sum(self._buckets.values()))

    def summary(self) -> dict:
        wall = self.wall_s()
        buckets = dict(self._buckets)
        buckets["other"] = max(0.0, wall - sum(buckets.values()))
        return {
            "wall_s": round(wall, 3),
            "buckets": {k: round(v, 3) for k, v in buckets.items()},
            "steps": self.steps,
            "productive_steps": max(0, self.steps - 1),
        }


def mfu(flops_per_step: float, step_time_s: float, *,
        generation: str, n_devices: int = 1) -> float:
    """Model FLOPs Utilization of one step against the roofline peak.

    ``flops_per_step`` is the whole-program count (XLA ``cost_analysis``
    convention — the same number ``tune.roofline.score`` consumes), so
    the peak is the full slice's: per-chip bf16 peak × device count.
    Carries roofline's §8 caveat: scan-containing programs undercount,
    making this a LOWER bound on true utilization.
    """
    if step_time_s <= 0 or flops_per_step <= 0 or n_devices <= 0:
        return 0.0
    hw = roofline.get_hardware(generation)
    return flops_per_step / (step_time_s * hw.bf16_flops * n_devices)


def hbm_util(bytes_per_step: float, step_time_s: float, *,
             generation: str, n_devices: int = 1) -> float:
    """HBM-roofline utilization ("bytes-MFU") of one step: the compiled
    program's ``cost_analysis`` bytes accessed over what the slice's HBM
    could stream in that time.  The bandwidth twin of :func:`mfu` — for
    bandwidth-bound programs (the ResNet-50 step, PERF.md §2) THIS is the
    number that says "fast as the hardware allows", and the remat policies
    in :mod:`tpuframe.mem` move it directly.  Same §8 caveat as ``mfu``:
    scan-containing programs undercount bytes, so this is a lower bound.
    """
    if step_time_s <= 0 or bytes_per_step <= 0 or n_devices <= 0:
        return 0.0
    hw = roofline.get_hardware(generation)
    return bytes_per_step / (step_time_s * hw.hbm_bytes_per_s * n_devices)


def flops_fallback(n_params: int, examples_per_step: int,
                   tokens_per_example: int = 1) -> float:
    """Analytic fwd+bwd flops estimate when the compiled program's
    cost_analysis is unavailable: the standard 6·N·D dense heuristic
    (2 flops/param forward + 4 backward, per processed token/example).
    An estimate — cost_analysis wins whenever it exists."""
    return 6.0 * float(n_params) * float(examples_per_step) \
        * float(tokens_per_example)


# ---------------------------------------------------------------------------
# Offline half: the same accounting recomputed from an event stream.
# ---------------------------------------------------------------------------

def _attempts(events: list[dict]) -> list[list[dict]]:
    """Split a merged stream into per-attempt sub-streams (ascending)."""
    by_attempt: dict[int, list[dict]] = {}
    for rec in events:
        by_attempt.setdefault(int(rec.get("attempt", 0)), []).append(rec)
    return [by_attempt[a] for a in sorted(by_attempt)]


def step_times_ms(events: list[dict], *,
                  include_first: bool = False) -> list[float]:
    """Per-step host wall ms from ``step`` events (first step — the
    compile — excluded unless asked; it would dominate any statistic)."""
    steps = [r for r in events if r.get("type") == "step"]
    if not include_first and steps:
        steps = steps[1:]
    return [float(r["wall_ms"]) for r in steps if "wall_ms" in r]


def from_events(events: list[dict], *,
                generation: str | None = None) -> dict:
    """Recompute the goodput breakdown from a (merged) event stream.

    Prefers the writer's own ``run_end`` summary when one exists (the
    live meter saw every boundary); otherwise reconstructs the buckets
    from ``step``/``ckpt_*``/``stall`` events — the crashed-attempt
    path, where no run_end was ever written.  Cross-attempt restart-lost
    time is computed here either way: for each non-final attempt,
    (that attempt's time past its last event) is unknowable, so the
    charge is the *gap* between its last event and the next attempt's
    first, plus any steps the relaunch retrained (visible as step
    indices replayed below the prior attempt's high-water mark).
    """
    out: dict = {"attempts": 0, "restart_lost_s": 0.0,
                 "retrained_steps": 0}
    attempts = _attempts(events)
    out["attempts"] = len(attempts)
    if not attempts:
        out["buckets"] = {b: 0.0 for b in BUCKETS}
        out["wall_s"] = 0.0
        out["steps"] = 0
        return out

    # Cross-attempt stitching.
    for prev, nxt in zip(attempts, attempts[1:]):
        prev_ts = [r["t"] for r in prev if "t" in r]
        nxt_ts = [r["t"] for r in nxt if "t" in r]
        if prev_ts and nxt_ts:
            out["restart_lost_s"] += max(0.0, min(nxt_ts) - max(prev_ts))
        prev_hi = max((int(r["step"]) for r in prev
                       if r.get("type") == "step"), default=0)
        replayed = [int(r["step"]) for r in nxt
                    if r.get("type") == "step" and int(r["step"]) <= prev_hi]
        out["retrained_steps"] += len(replayed)

    # Elastic resizes are attempt-boundary facts like restart-lost time:
    # surface them so ``summarize`` shows which attempts changed world
    # size (retrained_steps is the ≤1-lost-step check's numerator).
    resizes = [r for r in events if r.get("type") == "elastic_resize"]
    if resizes:
        out["elastic_resizes"] = len(resizes)
        out["elastic_transitions"] = [
            f"{int(r.get('n_from', 0))}->{int(r.get('n_to', 0))}"
            for r in resizes]

    # Per-attempt buckets, summed.
    buckets = {b: 0.0 for b in BUCKETS}
    wall = 0.0
    final_step = 0
    n_steps = 0
    mfu_productive = None
    mfu_goodput = None
    hbm_util_productive = None
    peak_hbm = None
    for stream in attempts:
        end = next((r for r in stream if r.get("type") == "run_end"), None)
        if end is not None:
            g = end.get("goodput", {})
            for k, v in g.get("buckets", {}).items():
                if k in buckets:
                    buckets[k] += float(v)
            wall += float(g.get("wall_s", end.get("wall_s", 0.0)))
            final_step = max(final_step, int(end.get("final_step", 0)))
            n_steps += int(g.get("steps") or
                           sum(1 for r in stream if r.get("type") == "step"))
            if end.get("mfu_productive") is not None:
                mfu_productive = float(end["mfu_productive"])
            if end.get("mfu_goodput") is not None:
                mfu_goodput = float(end["mfu_goodput"])
            if end.get("hbm_util_productive") is not None:
                hbm_util_productive = float(end["hbm_util_productive"])
            if end.get("peak_hbm_bytes") is not None:
                peak_hbm = max(peak_hbm or 0,
                               int(end["peak_hbm_bytes"]))
            continue
        # Crashed attempt: rebuild from raw events.  Buckets are
        # accumulated attempt-locally so a later crashed attempt can't
        # clobber an earlier attempt's recorded ``other``.
        ts = [r["t"] for r in stream if "t" in r]
        span = (max(ts) - min(ts)) if len(ts) > 1 else 0.0
        wall += span
        local = {b: 0.0 for b in BUCKETS if b != "other"}
        steps = [r for r in stream if r.get("type") == "step"]
        n_steps += len(steps)
        if steps:
            final_step = max(final_step,
                             max(int(r["step"]) for r in steps))
            local["compile"] += float(steps[0].get("wall_ms", 0.0)) / 1e3
            local["productive"] += sum(
                float(r.get("wall_ms", 0.0)) for r in steps[1:]) / 1e3
            # Schema v2: data-pipeline wait rides on each step record,
            # already excluded from its wall_ms; absent (v1) means zero.
            local["input"] += sum(
                float(r.get("input_wait_ms", 0.0)) for r in steps) / 1e3
        for r in stream:
            if r.get("type") == "ckpt_save":
                # ``block_ms`` (v2) is the slice the step path actually
                # waited — for async saves, just the snapshot; ``ms``
                # spans through commit, which for async runs mostly
                # overlaps training and must not be charged to ckpt.
                blocked = r.get("block_ms")
                if blocked is None:
                    blocked = 0.0 if r.get("async_write") \
                        else r.get("ms", 0.0)
                local["ckpt"] += float(blocked) / 1e3
            elif r.get("type") == "stall":
                local["stall"] += float(r.get("idle_s", 0.0))
        for k, v in local.items():
            buckets[k] += v
        buckets["other"] += max(0.0, span - sum(local.values()))
        for r in stream:
            if r.get("type") == "devmem":
                for dev in r.get("devices", []):
                    b = dev.get("peak_bytes_in_use",
                                dev.get("bytes_in_use"))
                    if b is not None:
                        peak_hbm = max(peak_hbm or 0, int(b))

    out["buckets"] = {k: round(v, 3) for k, v in buckets.items()}
    out["wall_s"] = round(wall, 3)
    out["steps"] = n_steps
    out["final_step"] = final_step
    if mfu_productive is not None:
        out["mfu_productive"] = mfu_productive
    if mfu_goodput is not None:
        out["mfu_goodput"] = mfu_goodput
    if hbm_util_productive is not None:
        out["hbm_util_productive"] = hbm_util_productive
    if peak_hbm is not None:
        out["peak_hbm_bytes"] = peak_hbm

    # Recompute MFU / HBM utilization offline when the manifest recorded
    # the cost models (run_start carries flops_per_step/bytes_per_step) —
    # lets ``summarize`` work on crashed logs that never wrote run_end.
    if mfu_productive is None or hbm_util_productive is None:
        start = next((r for r in events if r.get("type") == "run_start"),
                     None)
        times = step_times_ms(events)
        if start and times:
            # Logs from before run_start carried a generation were all
            # priced at roofline.ASSUMED_GENERATION.
            gen = (generation or start.get("generation")
                   or roofline.ASSUMED_GENERATION)
            mean_s = sum(times) / len(times) / 1e3
            n_dev = int(start.get("devices", 1))
            if mfu_productive is None and start.get("flops_per_step"):
                out["mfu_productive"] = mfu(
                    float(start["flops_per_step"]), mean_s,
                    generation=gen, n_devices=n_dev)
            if hbm_util_productive is None and start.get("bytes_per_step"):
                out["hbm_util_productive"] = hbm_util(
                    float(start["bytes_per_step"]), mean_s,
                    generation=gen, n_devices=n_dev)
    return out


# ---------------------------------------------------------------------------
# Anomaly detection — the "what went wrong" half of the analyzer.
# ---------------------------------------------------------------------------

def find_anomalies(events: list[dict], *, slow_factor: float = 3.0,
                   window: int = 16, retry_storm: int = 5,
                   retry_window_s: float = 60.0,
                   mfu_min: float | None = None,
                   blocked_ms: float = 1000.0) -> list[dict]:
    """Flag suspicious shapes in a merged event stream.

    Detectors (each finding: ``{"kind", "detail", ...anchors}``):

      step_regression — a step's wall ms exceeds ``slow_factor`` × the
        rolling median of the previous ``window`` steps (first/compile
        step excluded).  The rolling median, not the global one: a run
        that *gradually* slows (fragmenting HBM, growing host GC) trips
        the detector where a global median would absorb it.
      stall            — every heartbeat ``stall`` event.
      retry_storm      — ≥ ``retry_storm`` retry events inside any
        ``retry_window_s`` window: a flaky backend being hammered.
      low_mfu          — reported MFU below ``mfu_min`` (off by default;
        thresholds are workload policy, not a universal constant).
      no_run_end       — an attempt that never wrote ``run_end``: the
        run died (crash, preemption without commit, or still live).
      blocked_input    — a step waited > ``blocked_ms`` on the data
        pipeline (``input_wait_ms``): the loader can't keep up, the
        exact stall class arXiv:1909.09756 warns erases pod efficiency.
      blocked_ckpt     — a save blocked the step path > ``blocked_ms``
        (``block_ms``; sync saves' full ``ms``): checkpointing is on
        the step path — the async pipeline exists to make this ~0.
      goodput_invariant — an attempt's ``run_end`` buckets do not sum
        to its wall time.  Flagged loudly instead of renormalized: a
        violated partition means a double-charged or lost slice, and
        silently rescaling it would hide the accounting bug the
        invariant exists to catch.
      leaked_span / orphan_span — tracing-plane failure modes
        (``obs.tracing.span_anomalies``): a span opened with no close
        before the stream ended (a request a replica never answered, or
        a process that died holding it), and a close/child/note whose
        span or parent was never opened (a propagation bug or torn
        context).
    """
    findings: list[dict] = []

    steps = [r for r in events if r.get("type") == "step"
             and "wall_ms" in r]
    recent: list[float] = []
    for r in steps[1:]:
        ms = float(r["wall_ms"])
        if len(recent) >= 3:
            med = sorted(recent)[len(recent) // 2]
            if med > 0 and ms > slow_factor * med:
                findings.append({
                    "kind": "step_regression", "step": int(r["step"]),
                    "wall_ms": round(ms, 2),
                    "rolling_median_ms": round(med, 2),
                    "detail": f"step {r['step']} took {ms:.1f} ms — "
                              f"{ms / med:.1f}x the rolling median "
                              f"({med:.1f} ms)"})
        recent.append(ms)
        if len(recent) > window:
            recent.pop(0)

    for r in events:
        if r.get("type") == "stall":
            findings.append({
                "kind": "stall", "last_step": r.get("last_step"),
                "idle_s": r.get("idle_s"),
                "detail": f"heartbeat stall: no step for "
                          f"{r.get('idle_s', '?')}s after step "
                          f"{r.get('last_step', '?')}"})

    retries = sorted(float(r["t"]) for r in events
                     if r.get("type") == "retry" and "t" in r)
    lo = 0
    reported_storm = False
    for hi in range(len(retries)):
        while retries[hi] - retries[lo] > retry_window_s:
            lo += 1
        if hi - lo + 1 >= retry_storm and not reported_storm:
            findings.append({
                "kind": "retry_storm", "count": hi - lo + 1,
                "window_s": retry_window_s,
                "detail": f"{hi - lo + 1} I/O retries within "
                          f"{retry_window_s:.0f}s — storage backend "
                          f"degraded"})
            reported_storm = True  # one report per stream, not per pair

    if mfu_min is not None:
        summary = from_events(events)
        got = summary.get("mfu_productive")
        if got is not None and got < mfu_min:
            findings.append({
                "kind": "low_mfu", "mfu": round(got, 4),
                "threshold": mfu_min,
                "detail": f"MFU {got:.2%} below threshold "
                          f"{mfu_min:.2%}"})

    if blocked_ms is not None:
        for r in events:
            if (r.get("type") == "step"
                    and float(r.get("input_wait_ms") or 0.0) > blocked_ms):
                w = float(r["input_wait_ms"])
                findings.append({
                    "kind": "blocked_input", "step": r.get("step"),
                    "input_wait_ms": round(w, 2), "threshold_ms": blocked_ms,
                    "detail": f"step {r.get('step')} blocked {w:.0f} ms on "
                              f"the input pipeline (> {blocked_ms:.0f} ms)"})
            elif r.get("type") == "ckpt_save":
                blk = r.get("block_ms")
                if blk is None and not r.get("async_write"):
                    blk = r.get("ms")  # schema v1 sync save: all blocking
                if blk is not None and float(blk) > blocked_ms:
                    findings.append({
                        "kind": "blocked_ckpt", "step": r.get("step"),
                        "block_ms": round(float(blk), 2),
                        "threshold_ms": blocked_ms,
                        "detail": f"save at step {r.get('step')} blocked "
                                  f"the step path {float(blk):.0f} ms "
                                  f"(> {blocked_ms:.0f} ms)"})

    for stream in _attempts(events):
        if not any(r.get("type") == "run_end" for r in stream):
            att = stream[0].get("attempt", 0) if stream else 0
            last = max((int(r["step"]) for r in stream
                        if r.get("type") == "step"), default=None)
            findings.append({
                "kind": "no_run_end", "attempt": att, "last_step": last,
                "detail": f"attempt {att} never wrote run_end (died or "
                          f"still running); last seen step: {last}"})
            continue
        for end in (r for r in stream if r.get("type") == "run_end"):
            g = end.get("goodput", {})
            wall = float(g.get("wall_s", end.get("wall_s", 0.0)))
            total = sum(float(v) for v in g.get("buckets", {}).values())
            # The meter's ``other`` bucket absorbs the remainder, so the
            # partition is exact up to per-bucket rounding (3 decimals,
            # ≤ 0.5 ms each) — anything past that slack is a real
            # double-charge or lost slice, never noise.
            tol = max(0.05, 0.001 * len(g.get("buckets", {})) + 0.01)
            if g.get("buckets") and abs(total - wall) > tol:
                att = end.get("attempt", 0)
                findings.append({
                    "kind": "goodput_invariant", "attempt": att,
                    "wall_s": round(wall, 3), "bucket_sum_s": round(total, 3),
                    "detail": f"attempt {att} goodput buckets sum to "
                              f"{total:.3f}s but wall is {wall:.3f}s — "
                              f"bucket accounting violated (delta "
                              f"{total - wall:+.3f}s)"})

    if any(r.get("type") in ("span_open", "span_close", "span_note")
           for r in events):
        # Lazy on purpose: training-only logs never pay the import.
        from tpuframe.obs import tracing

        findings.extend(tracing.span_anomalies(events))
    return findings


# ---------------------------------------------------------------------------
# Serving latency accounting (tpuframe.serve's serve_* events).
# ---------------------------------------------------------------------------

def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


def serve_stats(events: list) -> dict | None:
    """TTFT/TPOT percentiles and token throughput from ``serve_*``
    events; None when the log carries no serving traffic (so training
    summaries stay serving-free).  TTFT = arrival to first token (the
    prefill + queueing number); TPOT = per-token decode cadence after
    the first.  tokens/sec/chip divides by the ``serve_summary`` device
    count — the serving analogue of MFU's per-chip normalization."""
    reqs = [r for r in events if r.get("type") == "serve_request"]
    steps = [r for r in events if r.get("type") == "serve_step"]
    summary = next((r for r in reversed(events)
                    if r.get("type") == "serve_summary"), None)
    if not (reqs or steps or summary is not None):
        return None

    ttft = sorted(float(r["ttft_ms"]) for r in reqs
                  if r.get("ttft_ms") is not None)
    tpot = sorted(float(r["tpot_ms"]) for r in reqs
                  if r.get("tpot_ms") is not None)

    tokens_per_s = None
    n_devices = 1
    if summary is not None:
        n_devices = max(1, int(summary.get("n_devices") or 1))
        if summary.get("tokens_per_s") is not None:
            tokens_per_s = float(summary["tokens_per_s"])
    if tokens_per_s is None and steps:
        # No summary (run died mid-serve): reconstruct from the steps.
        toks = sum(int(r.get("produced") or 0) + int(r.get("admitted") or 0)
                   for r in steps)
        wall_s = sum(float(r.get("wall_ms") or 0.0) for r in steps) / 1e3
        tokens_per_s = toks / wall_s if wall_s > 0 else None

    return {
        "requests": len(reqs),
        "steps": len(steps),
        "output_tokens": sum(int(r.get("output_tokens") or 0)
                             for r in reqs),
        "ttft_ms": {q: round(_pct(ttft, v), 3) for q, v in
                    (("p50", 0.5), ("p90", 0.9), ("p99", 0.99))}
        if ttft else None,
        "tpot_ms": {q: round(_pct(tpot, v), 3) for q, v in
                    (("p50", 0.5), ("p90", 0.9), ("p99", 0.99))}
        if tpot else None,
        "tokens_per_s": round(tokens_per_s, 2)
        if tokens_per_s is not None else None,
        "tokens_per_s_per_chip": round(tokens_per_s / n_devices, 2)
        if tokens_per_s is not None else None,
        "n_devices": n_devices,
    }


def fleet_stats(events: list) -> dict | None:
    """Router-level rollup of the fleet's ``router_*`` events; None when
    the log carries no router traffic.  ``lost`` is the fleet contract's
    headline number — admitted minus retired, which a healthy run keeps
    at zero through drain/redispatch — and shed is reported beside it
    because an explicitly shed request is *not* a lost one (it was never
    acknowledged)."""
    done = [r for r in events if r.get("type") == "router_request"]
    admits = sum(1 for r in events if r.get("type") == "router_admit")
    sheds = sum(1 for r in events if r.get("type") == "router_shed")
    drains = [r for r in events if r.get("type") == "router_drain"]
    hedges = sum(1 for r in events if r.get("type") == "router_hedge")
    redispatches = sum(1 for r in events
                       if r.get("type") == "router_redispatch")
    summary = next((r for r in reversed(events)
                    if r.get("type") == "router_summary"), None)
    if not (done or admits or sheds or summary is not None):
        return None

    with_ttft = sorted((r for r in done if r.get("ttft_ms") is not None),
                       key=lambda r: float(r["ttft_ms"]))
    ttft = [float(r["ttft_ms"]) for r in with_ttft]
    # Exemplars: each percentile row links the ACTUAL request at that
    # rank — its trace id (when traced) and rid — so "p99 regressed"
    # becomes "open this trace's waterfall", not a number with no story.
    exemplars = None
    if with_ttft:
        exemplars = {}
        for q, frac in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
            idx = min(len(with_ttft) - 1,
                      int(round(frac * (len(with_ttft) - 1))))
            rec = with_ttft[idx]
            exemplars[q] = {"id": rec.get("id"),
                            "trace": rec.get("trace"),
                            "ttft_ms": round(float(rec["ttft_ms"]), 3)}
    by_replica: dict = {}
    for r in done:
        name = str(r.get("replica"))
        by_replica[name] = by_replica.get(name, 0) + 1

    # Live-rollout accounting (PR 17): final per-replica weights version
    # and the mixed-version window — first replica on the new version to
    # last replica on it (the boundedness the rollout controller
    # proves).  None when the log carries no rollout traffic.
    versions = None
    ro_steps = [r for r in events if r.get("type") == "rollout_step"]
    ro_done = next((r for r in reversed(events)
                    if r.get("type") == "rollout_done"), None)
    ro_abort = next((r for r in reversed(events)
                     if r.get("type") == "rollout_abort"), None)
    if ro_steps or ro_done or ro_abort:
        by_rep_version: dict = {}
        swap_ts = []
        for r in ro_steps:
            phase = r.get("phase")
            if phase in ("swapped", "relaunched"):
                by_rep_version[str(r.get("replica"))] = r.get("version")
                if r.get("t") is not None:
                    swap_ts.append(float(r["t"]))
            elif phase == "rolled_back":
                by_rep_version[str(r.get("replica"))] = r.get("version")
        versions = {
            "by_replica": dict(sorted(by_rep_version.items())),
            "target": (ro_done or ro_abort or {}).get("version"),
            "mixed_window_s": round(max(swap_ts) - min(swap_ts), 3)
            if len(swap_ts) >= 2 else 0.0,
            "aborted": ro_abort is not None,
            "abort_metric": ro_abort.get("metric") if ro_abort else None,
        }
    return {
        "requests": len(done),
        "admitted": admits,
        "shed": sheds,
        "lost": admits - len(done),
        "hedged": hedges,
        "redispatched": redispatches,
        "drains": [{"replica": r.get("replica"),
                    "reason": r.get("reason")} for r in drains],
        "by_replica": dict(sorted(by_replica.items())),
        "versions": versions,
        "ttft_ms": {q: round(_pct(ttft, v), 3) for q, v in
                    (("p50", 0.5), ("p90", 0.9), ("p99", 0.99))}
        if ttft else None,
        "ttft_exemplars": exemplars,
    }


# ---------------------------------------------------------------------------
# Run comparison — the regression sentry (``python -m tpuframe.obs compare``).
# ---------------------------------------------------------------------------

# Thresholds are in the units of the metric they guard: percentage
# increase for latencies (a run B more than ``step_pct``% slower at p50
# or p90 regressed), absolute fraction for the productive share of wall,
# relative fraction for MFU.  Policy defaults, overridable per-call and
# per-CLI-flag — a latency-critical serving fleet will want tighter ones.
DEFAULT_COMPARE_THRESHOLDS = {
    "step_pct": 25.0,        # step-time p50/p90 increase (%)
    "productive_drop": 0.10,  # absolute drop in productive wall fraction
    "mfu_drop": 0.10,        # relative mfu_productive drop (fraction)
    "serve_pct": 25.0,       # serve TTFT/TPOT p90 increase (%)
}


def _compare_metrics(events: list[dict], *,
                     generation: str | None = None) -> dict:
    """The comparable facts of one merged stream, in one flat dict."""
    out: dict = {}
    times = sorted(step_times_ms(events))
    if times:
        out["step_p50_ms"] = _pct(times, 0.5)
        out["step_p90_ms"] = _pct(times, 0.9)
    summary = from_events(events, generation=generation)
    wall = summary.get("wall_s") or 0.0
    if wall > 0:
        out["productive_frac"] = \
            summary["buckets"].get("productive", 0.0) / wall
    if summary.get("mfu_productive") is not None:
        out["mfu_productive"] = summary["mfu_productive"]
    serve = serve_stats(events)
    if serve is not None:
        if serve.get("ttft_ms"):
            out["serve_ttft_p90_ms"] = serve["ttft_ms"]["p90"]
        if serve.get("tpot_ms"):
            out["serve_tpot_p90_ms"] = serve["tpot_ms"]["p90"]
    fleet = fleet_stats(events)
    if fleet is not None and fleet.get("ttft_ms"):
        # End-to-end (router queue wait + replica TTFT): the number the
        # chaos proof bounds at <=2x baseline under a replica kill.
        out["router_ttft_p90_ms"] = fleet["ttft_ms"]["p90"]
    return out


def compare_runs(a_events: list[dict], b_events: list[dict], *,
                 thresholds: dict | None = None,
                 generation: str | None = None) -> dict:
    """Diff run B against baseline A on goodput, step time, MFU and serve
    percentiles.  Returns ``{"metrics": {name: {"a", "b", ...}},
    "regressions": [...], "improvements": [...]}`` — a metric only
    participates when BOTH runs carry it (a training-only baseline never
    "regresses" against a run that added serving traffic)."""
    th = dict(DEFAULT_COMPARE_THRESHOLDS)
    th.update(thresholds or {})
    ma = _compare_metrics(a_events, generation=generation)
    mb = _compare_metrics(b_events, generation=generation)

    # (metric, kind, threshold): ``pct_increase`` flags B > A by more
    # than threshold %; ``abs_drop``/``rel_drop`` flag B < A by more than
    # an absolute / relative amount (higher-is-better metrics).
    checks = (
        ("step_p50_ms", "pct_increase", th["step_pct"]),
        ("step_p90_ms", "pct_increase", th["step_pct"]),
        ("productive_frac", "abs_drop", th["productive_drop"]),
        ("mfu_productive", "rel_drop", th["mfu_drop"]),
        ("serve_ttft_p90_ms", "pct_increase", th["serve_pct"]),
        ("serve_tpot_p90_ms", "pct_increase", th["serve_pct"]),
        ("router_ttft_p90_ms", "pct_increase", th["serve_pct"]),
    )
    out: dict = {"metrics": {}, "regressions": [], "improvements": []}
    for name, kind, threshold in checks:
        a, b = ma.get(name), mb.get(name)
        if a is None or b is None:
            continue
        entry = {"metric": name, "a": round(float(a), 4),
                 "b": round(float(b), 4), "threshold": threshold}
        out["metrics"][name] = entry
        if kind == "pct_increase":
            if a <= 0:
                continue
            delta_pct = 100.0 * (b - a) / a
            entry["delta_pct"] = round(delta_pct, 2)
            if delta_pct > threshold:
                entry["detail"] = (f"{name}: {a:.2f} -> {b:.2f} "
                                   f"(+{delta_pct:.1f}% > {threshold:.0f}%)")
                out["regressions"].append(entry)
            elif delta_pct < -threshold:
                out["improvements"].append(entry)
        elif kind == "abs_drop":
            entry["delta"] = round(float(b - a), 4)
            if a - b > threshold:
                entry["detail"] = (f"{name}: {a:.3f} -> {b:.3f} "
                                   f"(dropped {a - b:.3f} > {threshold})")
                out["regressions"].append(entry)
            elif b - a > threshold:
                out["improvements"].append(entry)
        else:  # rel_drop
            if a <= 0:
                continue
            rel = (a - b) / a
            entry["delta_rel"] = round(rel, 4)
            if rel > threshold:
                entry["detail"] = (f"{name}: {a:.4f} -> {b:.4f} "
                                   f"(-{100 * rel:.1f}% > "
                                   f"{100 * threshold:.0f}%)")
                out["regressions"].append(entry)
            elif rel < -threshold:
                out["improvements"].append(entry)
    return out
