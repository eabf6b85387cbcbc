"""Chaos harness: the CPU-mesh train loop under scheduled fault
sequences (docs/DESIGN.md "Async checkpointing & the flush contract").

Three properties of the async checkpoint pipeline, each proven under a
deterministic injected fault instead of asserted from code reading:

  * slow storage moves OFF the step path — ``goodput.productive`` of a
    slow-GCS run matches the no-fault run and the save's ``block_ms``
    stays tiny while its full span ``ms`` absorbs the injected delay
    (sync saves eat the same delay ON the step path, for contrast);
  * exact-continuation resume — SIGTERM with an upload in flight exits
    rc 14 only after ``flush()`` commits, and the resumed run's final
    loss equals an uninterrupted run's;
  * no acknowledged-but-unwritten checkpoint — a worker crash mid-upload
    leaves an uncommitted dir and NO ``ckpt_save`` event; stitched
    across attempts, every ``ckpt_save`` event maps to a
    committed-or-quarantined directory.

Fault schedules are seeded through ``TPUFRAME_FAULTS`` (times=/delay_s=
budgets, no wall-clock races), so every run here is reproducible.  The
process-killing faults (crash, double-SIGTERM) run under a subprocess
supervisor; the goodput comparison runs in-process on the shared
8-device CPU mesh.
"""

import os
import subprocess
import sys
import time

import numpy as np
import optax
import pytest

import jax.numpy as jnp

from tpuframe import ckpt
from tpuframe import train as train_mod
from tpuframe.ckpt.checkpoint import in_flight_step, latest_step
from tpuframe.launch import launcher as launcher_mod
from tpuframe.obs import events, goodput
from tpuframe.obs import metrics
from tpuframe.obs import tracing
from tpuframe.parallel import step as step_lib
from tpuframe.resilience import RC_PREEMPTED, faults
from tpuframe.utils import get_config


@pytest.fixture(autouse=True)
def _clean_chaos_state(monkeypatch):
    monkeypatch.delenv("TPUFRAME_FAULTS", raising=False)
    monkeypatch.delenv("TPUFRAME_ASYNC_CKPT", raising=False)
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    monkeypatch.delenv(events.ENV_ATTEMPT, raising=False)
    faults.reset_from_env()
    metrics.reset_counters("retry.")
    events.close()
    yield
    faults.reset_from_env({})
    metrics.reset_counters("retry.")
    events.close()


def _smoke_cfg(tmp_path, **over):
    over.setdefault("distributed", False)
    over.setdefault("log_every", 1000)
    over.setdefault("eval_every", 1000)
    over.setdefault("global_batch", 16)
    over.setdefault("ckpt_dir", str(tmp_path / "ck"))
    return get_config("smoke").with_overrides(**over)


def _run_train(workdir, *, steps, ckpt_every, attempt=0, extra_env=None,
               devices=4, sets=None):
    """One supervised training attempt in a subprocess (``devices`` CPU
    devices — per attempt, so elastic legs can resize the world), with
    its event log and checkpoint dir under ``workdir`` so relaunch
    attempts stitch into one stream.  ``sets`` overrides/extends the
    default ``--set`` config pairs."""
    env = dict(os.environ)
    env.pop("TPUFRAME_FAULTS", None)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": " ".join(flags).strip(),
        events.ENV_DIR: str(workdir / "events"),
        events.ENV_ATTEMPT: str(attempt),
    })
    env.update(extra_env or {})
    pairs = {"total_steps": steps, "ckpt_every": ckpt_every,
             "log_every": 2, "eval_every": 1000, "global_batch": 8,
             "distributed": False}
    pairs.update(sets or {})
    cmd = [sys.executable, "-m", "tpuframe.train", "--config", "smoke"]
    for k, v in pairs.items():
        cmd += ["--set", f"{k}={v}"]
    cmd += ["--ckpt-dir", str(workdir / "ck")]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=240)


def _final_loss(proc, step):
    line = next(l for l in proc.stdout.splitlines() if f"[train {step}]" in l)
    return float(line.split("loss=")[1].split()[0])


def _assert_commit_or_quarantine(ck_dir, merged):
    """The cross-attempt stitcher invariant: every acknowledged save
    (a ``ckpt_save`` event) corresponds to a committed-or-quarantined
    directory — never an acknowledged-but-unwritten checkpoint."""
    saves = [r for r in merged if r.get("type") == "ckpt_save"]
    assert saves, "no ckpt_save events to check"
    for r in saves:
        name = f"step_{int(r['step']):08d}"
        committed = (ck_dir / name / "COMMIT").exists()
        quarantined = (ck_dir / f"{name}.corrupt").is_dir()
        assert committed or quarantined, (
            f"ckpt_save event for step {r['step']} but {name} is neither "
            f"committed nor quarantined")


# ---------------------------------------------------------------------------
# Goodput proof: slow GCS off the step path (summarize comparison).
# ---------------------------------------------------------------------------


class TestSlowGcsGoodput:
    # 4 delayed writes x 0.3s land on the step-10 save; 30 post-save
    # steps (~2.5s of compute) give the async worker room to overlap.
    _FAULT = "slow_gcs:delay_s=0.3:times=4"
    _STEPS, _EVERY = 40, 10

    def _run(self, tmp_path, monkeypatch, tag, *, fault, ckpt_async):
        evdir = str(tmp_path / f"ev_{tag}")
        monkeypatch.setenv(events.ENV_DIR, evdir)
        if fault:
            monkeypatch.setenv("TPUFRAME_FAULTS", fault)
        else:
            monkeypatch.delenv("TPUFRAME_FAULTS", raising=False)
        out = train_mod.train(_smoke_cfg(
            tmp_path / tag, total_steps=self._STEPS,
            ckpt_every=self._EVERY, ckpt_async=ckpt_async))
        assert out["step"] == self._STEPS
        return events.merge(evdir)

    def test_async_moves_ckpt_wall_off_step_path(self, tmp_path,
                                                 monkeypatch):
        base = self._run(tmp_path, monkeypatch, "base",
                         fault=None, ckpt_async=True)
        slow_async = self._run(tmp_path, monkeypatch, "slow_async",
                               fault=self._FAULT, ckpt_async=True)
        slow_sync = self._run(tmp_path, monkeypatch, "slow_sync",
                              fault=self._FAULT, ckpt_async=False)

        g_base = goodput.from_events(base)
        g_async = goodput.from_events(slow_async)
        g_sync = goodput.from_events(slow_sync)

        # The injected 1.2s hits the sync run's step path...
        assert g_sync["buckets"]["ckpt"] > 1.0, g_sync["buckets"]
        # ...and stays off the async run's (snapshot blocking only).
        assert g_async["buckets"]["ckpt"] < 0.8, g_async["buckets"]
        # Productive time is storage-independent: the slow-GCS async run
        # matches the no-fault run within CPU-timing noise.
        p_base = g_base["buckets"]["productive"]
        p_async = g_async["buckets"]["productive"]
        assert abs(p_async - p_base) < max(1.0, 0.5 * p_base), (
            p_base, p_async)

        # Event-level evidence on the slowed save: the full span absorbs
        # the delay, the step path never saw it.
        slowed = next(r for r in slow_async
                      if r.get("type") == "ckpt_save"
                      and r["step"] == self._EVERY)
        assert slowed["async_write"] is True
        assert slowed["ms"] > 1000.0, slowed
        assert slowed["block_ms"] < 500.0, slowed
        assert slowed["ms"] > 3 * slowed["block_ms"]

        # The blocked_ckpt detector agrees: the sync run is flagged, the
        # async run is not — and the live meter's sums-to-wall invariant
        # holds everywhere (no goodput_invariant findings).
        kinds_sync = {f["kind"] for f in goodput.find_anomalies(slow_sync)}
        kinds_async = {f["kind"] for f in goodput.find_anomalies(slow_async)}
        assert "blocked_ckpt" in kinds_sync
        assert "blocked_ckpt" not in kinds_async
        assert "goodput_invariant" not in (kinds_sync | kinds_async)

        # Both fault runs recorded the injections (fault_injected is
        # emitted before the fault acts — even from the worker thread).
        assert sum(1 for r in slow_async
                   if r.get("type") == "fault_injected") == 4


# ---------------------------------------------------------------------------
# Crash mid-upload: no acknowledged-but-unwritten checkpoint.
# ---------------------------------------------------------------------------


def test_crash_during_upload_never_acknowledges(tmp_path):
    work = tmp_path
    crashed = _run_train(work, steps=6, ckpt_every=3, attempt=0,
                         extra_env={"TPUFRAME_ASYNC_CKPT": "1",
                                    "TPUFRAME_FAULTS":
                                    "crash_during_upload:times=1"})
    assert crashed.returncode == 42, crashed.stderr[-1500:]
    assert "FAULT INJECTION" in crashed.stdout

    ck = work / "ck"
    # The step-3 save died after its shard files, before sidecar/COMMIT:
    # visible to the supervisor's in-flight probe, invisible to resume.
    assert (ck / "step_00000003").is_dir()
    assert not (ck / "step_00000003" / "COMMIT").exists()
    assert latest_step(str(ck)) is None
    assert in_flight_step(str(ck)) == 3

    # The ckpt_save event is emitted only after COMMIT, so the crashed
    # attempt acknowledged nothing.
    attempt0 = [r for r in events.merge(str(work / "events"))
                if r["attempt"] == 0]
    assert not any(r["type"] == "ckpt_save" for r in attempt0)
    assert any(r["type"] == "fault_injected" for r in attempt0)

    # Relaunch: nothing committed, so the attempt retrains from scratch
    # and overwrites the torn step-3 leftovers on its way through.
    resumed = _run_train(work, steps=6, ckpt_every=3, attempt=1,
                         extra_env={"TPUFRAME_ASYNC_CKPT": "1"})
    assert resumed.returncode == 0, resumed.stderr[-1500:]
    assert latest_step(str(ck)) == 6

    merged = events.merge(str(work / "events"))
    assert {r["attempt"] for r in merged} == {0, 1}
    _assert_commit_or_quarantine(ck, merged)


# ---------------------------------------------------------------------------
# SIGTERM with a pending upload: rc 14 only after flush() commits, then
# exact-continuation resume (golden-loss equality).
# ---------------------------------------------------------------------------


def test_sigterm_pending_upload_flushes_then_resumes_exactly(tmp_path):
    straight = _run_train(tmp_path / "a", steps=6, ckpt_every=3,
                          extra_env={"TPUFRAME_ASYNC_CKPT": "1"})
    assert straight.returncode == 0, straight.stderr[-1500:]

    work = tmp_path / "b"
    # SIGTERM lands the instant the step-3 snapshot starts uploading;
    # the slow_gcs budget guarantees the upload is genuinely in flight
    # when the flag is checked at the step boundary.
    preempted = _run_train(
        work, steps=6, ckpt_every=3, attempt=0,
        extra_env={"TPUFRAME_ASYNC_CKPT": "1",
                   "TPUFRAME_FAULTS": "sigterm_pending_upload:times=1,"
                                      "slow_gcs:delay_s=0.5:times=2"})
    assert preempted.returncode == RC_PREEMPTED, preempted.stderr[-1500:]
    assert "FAULT INJECTION: raising SIGTERM" in preempted.stdout
    # rc 14 was only reached through flush(): the pending save is
    # committed (not quarantined) and therefore acknowledged.
    ck = work / "ck"
    assert (ck / "step_00000003" / "COMMIT").exists()
    assert not (ck / "step_00000003.corrupt").exists()
    attempt0 = [r for r in events.merge(str(work / "events"))
                if r["attempt"] == 0]
    assert any(r["type"] == "ckpt_save" and r["step"] == 3
               for r in attempt0)
    assert any(r["type"] == "preempt" for r in attempt0)
    assert any(r["type"] == "run_end" for r in attempt0)

    resumed = _run_train(work, steps=6, ckpt_every=3, attempt=1,
                         extra_env={"TPUFRAME_ASYNC_CKPT": "1"})
    assert resumed.returncode == 0, resumed.stderr[-1500:]
    assert "resumed from step 3" in resumed.stdout
    np.testing.assert_allclose(_final_loss(resumed, 6),
                               _final_loss(straight, 6), rtol=1e-4)

    _assert_commit_or_quarantine(ck, events.merge(str(work / "events")))


# ---------------------------------------------------------------------------
# flush() unit contract: commit-or-quarantine at the deadline.
# ---------------------------------------------------------------------------


def _toy_state():
    return step_lib.TrainState.create(
        {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(())},
        optax.adam(1e-3))


class TestFlush:
    def test_flush_commits_and_returns_true(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), async_write=True)
        state = _toy_state()
        mgr.save(1, state)
        assert mgr.flush(deadline_s=30.0) is True
        assert (tmp_path / "step_00000001" / "COMMIT").exists()
        assert mgr._pending == []
        step, _ = mgr.restore_latest(target=state)
        assert step == 1

    def test_flush_sync_manager_is_trivial(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, _toy_state())
        assert mgr.flush(deadline_s=0.0) is True
        assert (tmp_path / "step_00000001" / "COMMIT").exists()

    def test_flush_deadline_quarantines_stranded_upload(self, tmp_path,
                                                        monkeypatch,
                                                        capsys):
        # The worker wedges forever inside its first storage write (kind
        # hang on the slow_gcs seam); flush must not wait on it past the
        # deadline, and must leave nothing resume could mistake for a
        # durable checkpoint.  The hung daemon thread never wakes again,
        # so it cannot recreate the dir behind the test's back.
        monkeypatch.setenv("TPUFRAME_FAULTS", "slow_gcs:kind=hang:times=1")
        faults.reset_from_env()
        mgr = ckpt.CheckpointManager(str(tmp_path), async_write=True)
        mgr.save(1, _toy_state())
        t0 = time.perf_counter()
        assert mgr.flush(deadline_s=0.5) is False
        assert time.perf_counter() - t0 < 5.0  # bounded, not a join()
        assert (tmp_path / "step_00000001.corrupt").is_dir()
        assert not (tmp_path / "step_00000001").exists()
        assert latest_step(str(tmp_path)) is None
        assert in_flight_step(str(tmp_path)) is None
        assert mgr._pending == []
        assert "quarantined" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# The supervisor's probe understands in-flight saves.
# ---------------------------------------------------------------------------


class TestInFlightProbe:
    def test_in_flight_step_ignores_committed_and_corrupt(self, tmp_path):
        os.makedirs(tmp_path / "step_00000004")
        (tmp_path / "step_00000004" / "COMMIT").write_text("done")
        os.makedirs(tmp_path / "step_00000007")  # upload in flight
        os.makedirs(tmp_path / "step_00000005.corrupt")  # quarantined
        assert latest_step(str(tmp_path)) == 4
        assert in_flight_step(str(tmp_path)) == 7
        assert in_flight_step(str(tmp_path / "absent")) is None

    def test_progress_probe_counts_in_flight_saves(self, tmp_path):
        probe = launcher_mod._progress_probe(
            ["prog", "--ckpt-dir", str(tmp_path)])
        assert probe() is None  # empty dir: no progress yet
        os.makedirs(tmp_path / "step_00000010")
        (tmp_path / "step_00000010" / "COMMIT").write_text("done")
        assert probe() == 10
        # A preempted-mid-upload step counts as progress: the relaunch
        # either finishes the commit or retrains a few steps — it is not
        # a crash loop, and the budget must not be charged as one.
        os.makedirs(tmp_path / "step_00000020")
        assert probe() == 20
        # ...but a quarantined dir never does.
        os.rename(tmp_path / "step_00000020",
                  tmp_path / "step_00000020.corrupt")
        assert probe() == 10


# ---------------------------------------------------------------------------
# Elastic resize: 8 -> 4 -> 8 devices across relaunches, losing <=1 step
# per boundary, golden-loss-equivalent to the uninterrupted 8-device run.
# ---------------------------------------------------------------------------


class TestElasticResize:
    """The drain -> relaunch -> reshard -> rescale contract, end to end.

    Each leg is a subprocess at its own forced device count; the legs
    share the checkpoint dir and event dir, so the resize is detected by
    ``build_harness`` from the committed manifest's world record.  ZeRO-1
    weight update makes the reshard real: the smoke convnet's bias (size
    10) pads to 16 at n=8 and 12 at n=4, so both shrink and grow move a
    genuinely re-padded flat moment vector.  ``hold`` (the default
    policy) keeps batch/LR fixed, and the world-size-invariant loader
    order makes the continued run golden-loss-comparable to a straight
    8-device run (FP reduction order differs across n, hence rtol).
    Dropout is disabled: its per-replica streams are decorrelated by
    axis index, so masks are world-size dependent by design and would
    break golden equivalence for a reason unrelated to resharding."""

    _STEPS, _EVERY = 9, 3
    # ckpt_keep covers every save across the three legs (up to two extra
    # drain saves at the preemption boundaries) so the commit-or-
    # quarantine sweep can audit all of them.
    _SETS = {"distributed": True, "model_kwargs": {"dropout": 0.0},
             "ckpt_keep": 8}
    _ENV = {"TPUFRAME_ASYNC_CKPT": "1",
            "TPUFRAME_WEIGHT_UPDATE": "zero1"}

    def _leg(self, work, *, attempt, devices, fault=None):
        extra = dict(self._ENV)
        if fault:
            extra["TPUFRAME_FAULTS"] = fault
        return _run_train(work, steps=self._STEPS, ckpt_every=self._EVERY,
                          attempt=attempt, devices=devices, sets=self._SETS,
                          extra_env=extra)

    def test_shrink_then_grow_continues_within_one_step(self, tmp_path):
        straight = self._leg(tmp_path / "a", attempt=0, devices=8)
        assert straight.returncode == 0, straight.stderr[-1500:]

        work = tmp_path / "b"
        # Leg 0 (8 devices): partial SIGTERM (k=1 of 1 local host) at
        # step 4 — the membership-change model; the preemption path
        # drains the async save before exiting rc 14.
        leg0 = self._leg(work, attempt=0, devices=8,
                         fault="host:step=4:kind=partial_sigterm:times=1")
        assert leg0.returncode == RC_PREEMPTED, leg0.stderr[-1500:]
        assert "FAULT INJECTION" in leg0.stdout
        ck = work / "ck"
        committed0 = latest_step(str(ck))
        assert committed0 is not None and committed0 >= 3

        # Leg 1 (4 devices): restore reshards zero1 state 8->4 and the
        # run continues; a second reclaim ends the leg.
        leg1 = self._leg(work, attempt=1, devices=4,
                         fault="host:step=7:kind=partial_sigterm:times=1")
        assert leg1.returncode == RC_PREEMPTED, leg1.stderr[-1500:]
        assert "elastic resize: 8" in leg1.stdout, leg1.stdout[-2000:]
        assert "resumed from step" in leg1.stdout

        # Leg 2 (8 devices): capacity returns; reshard 4->8, run out.
        leg2 = self._leg(work, attempt=2, devices=8)
        assert leg2.returncode == 0, leg2.stderr[-1500:]
        assert "elastic resize: 4" in leg2.stdout, leg2.stdout[-2000:]
        assert "resumed from step" in leg2.stdout
        assert latest_step(str(ck)) == self._STEPS

        # Golden-loss-equivalent continuation under hold: same data
        # order (world-size-invariant loader), same batch/LR — only the
        # cross-n FP reduction order differs.
        np.testing.assert_allclose(_final_loss(leg2, self._STEPS),
                                   _final_loss(straight, self._STEPS),
                                   rtol=1e-3)

        merged = events.merge(str(work / "events"))
        assert {r["attempt"] for r in merged} == {0, 1, 2}
        _assert_commit_or_quarantine(ck, merged)

        # The typed boundary events carry full provenance.
        resizes = [r for r in merged if r["type"] == "elastic_resize"]
        assert [(r["n_from"], r["n_to"]) for r in resizes] == [(8, 4),
                                                              (4, 8)]
        for r in resizes:
            assert r["policy"] == "hold"
            assert r["global_batch_from"] == r["global_batch_to"] == 8
            assert r["base_lr_from"] == r["base_lr_to"]

        # The attempt stitcher prices the boundary: <=1 retrained step
        # per resize, and the stitcher surfaces the transitions.
        g = goodput.from_events(merged)
        assert g["attempts"] == 3
        assert g["retrained_steps"] <= 2, g
        assert g["elastic_resizes"] == 2
        assert g["elastic_transitions"] == ["8->4", "4->8"]

        # obs compare prices the boundary.  productive_frac is unchanged
        # in the amortized limit: its two factors are per-step productive
        # cost (asserted here — the resized legs' step path is not
        # slower, generous 3x bound because tiny CPU steps are noisy) and
        # boundary overhead (already bounded: retrained_steps <= 1 per
        # boundary plus a fixed init/compile cost per attempt, which at
        # this 9-step toy scale dominates wall but vanishes at real run
        # lengths — so the raw toy-scale fraction is NOT asserted).
        straight_ev = events.merge(str(tmp_path / "a" / "events"))
        cmp = goodput.compare_runs(straight_ev, merged)
        assert "productive_frac" in cmp["metrics"]
        g_straight = goodput.from_events(straight_ev)
        assert g["steps"] >= self._STEPS and g_straight["steps"] >= 1
        per_step = g["buckets"]["productive"] / g["steps"]
        per_step_straight = (g_straight["buckets"]["productive"]
                             / g_straight["steps"])
        assert per_step <= 3 * per_step_straight, (
            g["buckets"], g["steps"], g_straight["buckets"])


# ---------------------------------------------------------------------------
# Fleet chaos: kill 1 of 3 serving replicas mid-load, lose nothing.
# ---------------------------------------------------------------------------


class TestFleetChaos:
    """The serving half of the fault-tolerance story (DESIGN.md "Serving
    fleet & failure model"): a 3-replica fake-engine fleet under a
    seeded burst load, with ``replica_crash`` scheduled on one replica —
    deterministic via the fault registry's step pin, no wall-clock race.

    Proven against the same-seed no-fault run:
      * zero accepted-request loss — every admitted request retires
        exactly once (rid-level, through the event stitcher);
      * p99 TTFT of the faulted run stays <= 2x the no-fault run (burst
        load makes both queueing-dominated, so the bound tracks the 3->2
        capacity drop plus detection cost, not a noise floor);
      * the drain/redispatch story is visible as typed router_* events
        that validate_files, fleet_stats and obs compare all understand.
    """

    _N, _SEED = 36, 7
    _FLEET = dict(replicas=3, n_requests=_N, seed=_SEED, slots=2,
                  step_delay_ms=20.0, rate=1000.0,  # burst: all at t~0
                  max_new_tokens=8, queue_limit=256, hedge_ms=5000.0,
                  scrape_interval_s=0.05, timeout_s=90.0)

    def _events_ok(self, events_dir):
        files = events.event_files(str(events_dir))
        assert files, "fleet run wrote no event files"
        assert events.validate_files(files) == []
        return events.merge(str(events_dir))

    def test_replica_kill_loses_nothing_and_bounds_p99(self, tmp_path):
        from tpuframe.serve import router as router_lib

        base = router_lib.fleet_smoke(
            events_dir=str(tmp_path / "a"), **self._FLEET)
        kill = router_lib.fleet_smoke(
            events_dir=str(tmp_path / "b"), kill_rank=1, kill_step=3,
            **self._FLEET)

        # Clean fleet first: everything admitted, retired, exited 0.
        assert base["admitted"] == self._N and base["lost"] == 0
        assert base["shed"] == 0 and not base["timed_out"]
        assert base["exit_codes"] == [0, 0, 0]

        # The kill is real (os._exit(42) from the fault registry) ...
        assert kill["exit_codes"][1] == 42
        assert kill["exit_codes"][0] == 0 and kill["exit_codes"][2] == 0
        assert kill["drains"] >= 1
        # ... and still: zero accepted-request loss, shed counted (none
        # expected at this queue bound), nothing silently dropped.
        assert kill["admitted"] == self._N
        assert kill["lost"] == 0 and not kill["timed_out"]
        assert kill["shed"] == 0
        assert kill["requests"] + kill["shed"] == kill["admitted"]

        # p99 TTFT: faulted <= 2x no-fault, same seed.  _pct at p99 over
        # 36 samples is the max — this bounds the WORST request against
        # the capacity drop, not an average.
        p99_a = base["ttft_ms"]["p99"]
        p99_b = kill["ttft_ms"]["p99"]
        assert p99_a > 0
        assert p99_b <= 2.0 * p99_a, (
            f"p99 TTFT {p99_b:.1f}ms > 2x no-fault {p99_a:.1f}ms")

        # rid-exactness through the stitcher: every admitted rid retired
        # exactly once, across both the surviving replicas.
        merged = self._events_ok(tmp_path / "b")
        admits = [r["id"] for r in merged if r["type"] == "router_admit"]
        dones = [r["id"] for r in merged
                 if r["type"] == "router_request"]
        assert sorted(admits) == sorted(set(admits))
        assert sorted(dones) == sorted(admits)   # exactly once, all of them

        # The drain and re-dispatch are typed, attributed events.
        drains = [r for r in merged if r["type"] == "router_drain"]
        assert any(d["replica"] == "r1" for d in drains)
        assert all(d["reason"] for d in drains)
        redispatched = [r for r in merged
                        if r["type"] == "router_redispatch"]
        assert len(redispatched) == kill["redispatched"]
        # Dead replica's orphans landed on survivors.
        assert {r["replica"] for r in redispatched} <= {"r0", "r2"}

        # The offline analyzers see the same story.
        fleet = goodput.fleet_stats(merged)
        assert fleet["lost"] == 0 and fleet["requests"] == self._N
        assert any(d["replica"] == "r1" for d in fleet["drains"])
        assert set(fleet["by_replica"]) <= {"r0", "r2"}

        base_merged = self._events_ok(tmp_path / "a")
        cmp = goodput.compare_runs(base_merged, merged)
        assert "router_ttft_p90_ms" in cmp["metrics"]
        entry = cmp["metrics"]["router_ttft_p90_ms"]
        assert entry["a"] > 0 and entry["b"] > 0

        # Tracing through the kill: every admitted rid still
        # reconstructs to exactly ONE complete request root, every
        # completed root's wait+queue+prefill sum agrees with its
        # queue-inclusive TTFT (zero ttft_mismatch — the one-monotonic-
        # clock reconciliation), and the only anomalies are leaked
        # serve-side spans on the KILLED replica — the loud orphaned-
        # work signal the leak detector exists for.
        findings = tracing.verify_traces(merged)
        other = [f for f in findings if f["kind"] != "leaked_span"]
        assert other == [], other
        leaked = [f for f in findings if f["kind"] == "leaked_span"]
        assert leaked, "kill left no leaked span — the crash was clean?"
        assert all(str(f.get("host", "")).endswith("-p1")
                   for f in leaked), leaked
        traces = tracing.build_traces(merged)
        for rec in merged:
            if rec["type"] == "router_admit":
                roots = traces[rec["trace"]].complete_roots()
                assert len(roots) == 1, (rec["id"], len(roots))
        # The p99 exemplar names a trace the reconstruction can resolve.
        assert fleet["ttft_exemplars"]["p99"]["trace"] in traces
        # The no-fault run is anomaly-free end to end.
        assert tracing.verify_traces(base_merged) == []

    def test_replica_crash_seam_is_deterministic(self):
        """The seam grammar: replica_crash defaults to kind=crash and
        honors the step pin — the property the fleet test's kill_step
        scheduling rests on."""
        (f,) = faults.parse("replica_crash:step=3:rank=1")
        assert f.kind == "crash" and f.step == 3 and f.rank == 1
        for seam, kind in (("replica_hang", "hang"),
                           ("replica_slow", "slow")):
            (g,) = faults.parse(seam)
            assert g.kind == kind


class TestRollingUpdate:
    """PR 17's chaos tier: a live weight rollout across the 3-replica
    fleet under the same seeded burst load as TestFleetChaos, triggered
    the production way — the harness "commits" a checkpoint mid-run
    (manifest first, COMMIT last) and the controller's
    ``committed_world()`` poll picks it up.

    Proven, per ISSUE 17's acceptance bar:
      * zero accepted-request loss straight through the roll (rid-exact
        through the event stitcher);
      * p99 TTFT during the roll <= 2x the same-seed steady-state run;
      * every replica ends on the new version at ZERO compile-cache
        misses (hot swap, not restart), with the mixed-version window
        bounded and visible in fleet_stats;
      * a seeded-slow poisoned canary auto-rolls back — rollout_abort
        names the failing gate metric and the fleet returns to v0;
      * a replica killed mid-swap (rc 42) is drained, its work
        redispatched, and it relaunches on the NEW version — still
        zero loss.
    """

    _N, _SEED = 36, 7
    _ROLL = dict(replicas=3, n_requests=_N, seed=_SEED, slots=2,
                 step_delay_ms=20.0, rate=1000.0,  # burst: all at t~0
                 max_new_tokens=8, queue_limit=256, hedge_ms=5000.0,
                 scrape_interval_s=0.05, timeout_s=90.0,
                 canary_frac=0.34, bake_min_samples=4)

    def _steady(self, events_dir):
        from tpuframe.serve import router as router_lib

        keys = ("replicas", "n_requests", "seed", "slots",
                "step_delay_ms", "rate", "max_new_tokens", "queue_limit",
                "hedge_ms", "scrape_interval_s", "timeout_s")
        return router_lib.fleet_smoke(
            events_dir=str(events_dir),
            **{k: self._ROLL[k] for k in keys})

    def _events_ok(self, events_dir):
        files = events.event_files(str(events_dir))
        assert files, "rollout run wrote no event files"
        assert events.validate_files(files) == []
        return events.merge(str(events_dir))

    def _rid_exact(self, merged):
        admits = [r["id"] for r in merged if r["type"] == "router_admit"]
        dones = [r["id"] for r in merged if r["type"] == "router_request"]
        assert sorted(admits) == sorted(set(admits))
        assert sorted(dones) == sorted(admits)

    def test_rolling_update_zero_loss_bounded_p99(self, tmp_path):
        from tpuframe.serve import rollout as rollout_lib

        steady = self._steady(tmp_path / "steady")
        assert steady["lost"] == 0 and not steady["timed_out"]

        watch = tmp_path / "ck"
        watch.mkdir()
        # Mid-commit checkpoint on disk BEFORE the fleet starts: the
        # watcher must stay blind to it for the whole pre-trigger
        # window (the harness lands COMMIT mid-load).
        d = watch / "step_00000001"
        d.mkdir()
        (d / "manifest.json").write_text(
            '{"step": 1, "world": {"processes": 1, "devices": 1}}')

        out = rollout_lib.rolling_update_smoke(
            events_dir=str(tmp_path / "roll"), watch_dir=str(watch),
            gate_pct=50.0, **self._ROLL)
        ro = out["rollout"]

        # The roll completed the production way and nothing was lost.
        assert ro["state"] == "done" and ro["version"] == 1
        assert ro["world"]["step"] == 1
        assert out["admitted"] == self._N and out["lost"] == 0
        assert out["shed"] == 0 and not out["timed_out"]
        # Every replica ended on the new version — live off each
        # replica's own gauge, not the controller's belief.
        assert out["final_versions"] == {"r0": 1, "r1": 1, "r2": 1}
        # Hot swap, not restart: zero compile-cache misses, no relaunch.
        assert ro["swap_compile_misses"] == 0
        assert ro["relaunches"] == 0 and out["exit_codes"] == [0, 0, 0]
        # Bounded mixed-version window: one replica at a time.
        assert ro["window_s"] is not None and 0.0 < ro["window_s"] < 30.0

        # p99 TTFT during the roll <= 2x steady state, same seed.
        p99_a = steady["ttft_ms"]["p99"]
        p99_b = out["ttft_ms"]["p99"]
        assert p99_a > 0
        assert p99_b <= 2.0 * p99_a, (
            f"p99 TTFT {p99_b:.1f}ms during roll > 2x steady-state "
            f"{p99_a:.1f}ms")

        # rid-exactness and the typed rollout story in one stream.
        merged = self._events_ok(tmp_path / "roll")
        self._rid_exact(merged)
        ro_steps = [r for r in merged if r["type"] == "rollout_step"]
        assert [r for r in merged if r["type"] == "rollout_done"]
        swapped = [r["replica"] for r in ro_steps
                   if r["phase"] == "swapped"]
        assert sorted(swapped) == ["r0", "r1", "r2"]
        assert [r["replica"] for r in ro_steps
                if r["phase"] == "promoted"] == ["r0"]

        # The offline analyzers reconstruct the same bounded window.
        fs = goodput.fleet_stats(merged)
        assert fs["lost"] == 0
        v = fs["versions"]
        assert v["by_replica"] == {"r0": 1, "r1": 1, "r2": 1}
        assert v["target"] == 1 and not v["aborted"]
        assert 0.0 < v["mixed_window_s"] < 30.0

        # Tracing through the roll is fully clean: no leaks, no
        # orphans, every admitted rid exactly one complete root, every
        # phase sum within tolerance of its queue-inclusive TTFT —
        # drains and re-queues included.
        assert tracing.verify_traces(merged) == []
        traces = tracing.build_traces(merged)
        for rec in merged:
            if rec["type"] == "router_admit":
                assert len(traces[rec["trace"]].complete_roots()) == 1
        # The rollout itself is one force-sampled trace: a complete
        # root span whose notes carry the per-replica phases.
        ro_roots = [(tv, sp) for tv in traces.values()
                    for sp in tv.roots if sp.name == "rollout"]
        assert len(ro_roots) == 1
        rtv, ro_root = ro_roots[0]
        assert ro_root.complete
        assert ro_root.closed["status"] == "done"
        assert ro_root.closed["version"] == 1
        phases = {(n.get("replica"), n["note"]) for n in rtv.notes}
        assert {("r0", "swapped"), ("r1", "swapped"),
                ("r2", "swapped")} <= phases

    def test_poisoned_canary_auto_rolls_back(self, tmp_path):
        from tpuframe.serve import rollout as rollout_lib

        out = rollout_lib.rolling_update_smoke(
            events_dir=str(tmp_path / "ev"), gate_pct=50.0,
            faults_spec="slow_canary:times=1000:delay_s=0.05",
            **self._ROLL)
        ro = out["rollout"]

        # The gate caught the regression and named the metric.
        assert ro["state"] == "aborted" and ro["aborted"]
        assert ro["abort_metric"] in rollout_lib.GATE_METRICS
        # The fleet is back on the old version everywhere, and the
        # canary's last phase is the rollback.
        assert out["final_versions"] == {"r0": 0, "r1": 0, "r2": 0}
        assert ro["phases"][-1] == ["r0", "rolled_back"] or \
            tuple(ro["phases"][-1]) == ("r0", "rolled_back")
        # Still zero loss: a rollback is a drain, not an outage.
        assert out["admitted"] == self._N and out["lost"] == 0
        assert not out["timed_out"] and out["exit_codes"] == [0, 0, 0]

        merged = self._events_ok(tmp_path / "ev")
        self._rid_exact(merged)
        (abort,) = [r for r in merged if r["type"] == "rollout_abort"]
        assert abort["metric"] == ro["abort_metric"]
        assert abort["version"] == 1 and abort["reason"]
        v = goodput.fleet_stats(merged)["versions"]
        assert v["aborted"] and v["abort_metric"] == ro["abort_metric"]
        assert v["by_replica"]["r0"] == 0

    def test_mid_swap_kill_relaunches_on_new_version(self, tmp_path):
        from tpuframe.serve import rollout as rollout_lib

        out = rollout_lib.rolling_update_smoke(
            events_dir=str(tmp_path / "ev"), gate_pct=50.0,
            kill_during_swap_rank=1, **self._ROLL)
        ro = out["rollout"]

        # The kill was real (os._exit(42) inside swap application), the
        # supervisor relaunched rank 1 on the NEW version, and the roll
        # finished with every replica on it.
        assert out["relaunched_ranks"] == [1]
        assert ro["relaunches"] == 1
        assert ro["state"] == "done" and ro["version"] == 1
        assert out["final_versions"] == {"r0": 1, "r1": 1, "r2": 1}
        # Zero accepted-request loss through drain + kill + relaunch.
        assert out["admitted"] == self._N and out["lost"] == 0
        assert out["shed"] == 0 and not out["timed_out"]

        merged = self._events_ok(tmp_path / "ev")
        self._rid_exact(merged)
        ro_steps = [r for r in merged if r["type"] == "rollout_step"]
        assert [r["replica"] for r in ro_steps
                if r["phase"] == "swap_failed"] == ["r1"]
        assert [r["replica"] for r in ro_steps
                if r["phase"] == "relaunched"] == ["r1"]
        # The relaunch participates in the mixed-version window.
        v = goodput.fleet_stats(merged)["versions"]
        assert v["by_replica"] == {"r0": 1, "r1": 1, "r2": 1}
