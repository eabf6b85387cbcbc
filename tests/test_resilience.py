"""tpuframe.resilience: retry policies, structured fault injection, the
preemption contract, checkpoint quarantine/walk-back, and the hardened
supervisor (docs/DESIGN.md "Failure model & resilience").

Everything here is fast tier-1: recovery demos run the smoke workload
in-process on the virtual CPU mesh; timing behavior uses fake clocks.
"""

import json
import os
import random
import signal
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe import ckpt
from tpuframe import train as train_mod
from tpuframe.data import gcs
from tpuframe.launch.launcher import run_with_relaunch
from tpuframe.obs import metrics
from tpuframe.obs.heartbeat import Heartbeat
from tpuframe.parallel import step as step_lib
from tpuframe.resilience import RC_PREEMPTED, PreemptionGuard, RetryPolicy
from tpuframe.resilience import faults
from tpuframe.resilience.policy import is_retryable
from tpuframe.utils import get_config


@pytest.fixture(autouse=True)
def _clean_resilience_state(monkeypatch):
    """Every test starts with no armed faults and zeroed retry counters,
    and leaves none behind for the rest of the suite."""
    monkeypatch.delenv("TPUFRAME_FAULTS", raising=False)
    monkeypatch.delenv("TPUFRAME_FAULT_STEP", raising=False)
    monkeypatch.delenv("TPUFRAME_FAULT_ONCE", raising=False)
    faults.reset_from_env()
    metrics.reset_counters("retry.")
    yield
    faults.reset_from_env({})
    metrics.reset_counters("retry.")


# ---------------------------------------------------------------------------
# RetryPolicy: classification and timing (fake clock — no real sleeps)
# ---------------------------------------------------------------------------


class _FakeTime:
    def __init__(self):
        self.now = 0.0
        self.sleeps = []

    def clock(self):
        return self.now

    def sleep(self, s):
        self.sleeps.append(s)
        self.now += s


class _FixedRng:
    """uniform() returns the upper bound — makes jitter deterministic."""

    def uniform(self, a, b):
        return b


def _policy(ft, **kw):
    kw.setdefault("max_attempts", 4)
    kw.setdefault("base_delay_s", 0.1)
    kw.setdefault("max_delay_s", 10.0)
    kw.setdefault("deadline_s", 1000.0)
    return RetryPolicy(clock=ft.clock, sleep=ft.sleep, rng=_FixedRng(), **kw)


class TestRetryPolicy:
    def test_transient_failure_recovers(self):
        ft = _FakeTime()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionResetError("peer reset")
            return "ok"

        metrics.reset_counters("retry.")
        assert _policy(ft).call(flaky, op="t") == "ok"
        assert len(calls) == 3
        got = metrics.counters("retry.")
        assert got["retry.t.retries"] == 2
        assert got["retry.t.recovered"] == 1

    def test_backoff_is_exponential_with_cap(self):
        ft = _FakeTime()

        def always():
            raise TimeoutError("slow")

        with pytest.raises(TimeoutError):
            _policy(ft, max_attempts=6, max_delay_s=1.0).call(always, op="t")
        # _FixedRng takes the top of [base, prev*3] each round, so delays
        # triple until the cap: 0.3, 0.9, 1.0, 1.0, 1.0 (5 sleeps, 6 tries).
        np.testing.assert_allclose(ft.sleeps, [0.3, 0.9, 1.0, 1.0, 1.0])

    def test_deadline_stops_retrying_early(self):
        ft = _FakeTime()
        calls = []

        def always():
            calls.append(1)
            ft.now += 30.0  # each attempt burns 30s of fake time
            raise TimeoutError("slow")

        with pytest.raises(TimeoutError):
            _policy(ft, max_attempts=100, deadline_s=60.0).call(always, op="t")
        assert len(calls) < 5  # nowhere near 100 attempts
        assert metrics.counters("retry.")["retry.t.exhausted"] == 1

    def test_non_retryable_raises_immediately(self):
        ft = _FakeTime()
        calls = []

        def missing():
            calls.append(1)
            raise FileNotFoundError("no such object")

        with pytest.raises(FileNotFoundError):
            _policy(ft).call(missing, op="t")
        assert len(calls) == 1 and ft.sleeps == []

    def test_classification(self):
        assert is_retryable(ConnectionResetError("x"))
        assert is_retryable(TimeoutError("x"))
        assert is_retryable(OSError("generic I/O"))
        assert is_retryable(faults.InjectedFault("x"))
        assert not is_retryable(FileNotFoundError("x"))
        assert not is_retryable(PermissionError("x"))
        assert not is_retryable(ValueError("x"))
        # google-cloud transients are classified by class name, so the
        # check works without the library installed.
        ServiceUnavailable = type("ServiceUnavailable", (Exception,), {})
        assert is_retryable(ServiceUnavailable("503"))


# ---------------------------------------------------------------------------
# Fault spec parsing + the legacy alias
# ---------------------------------------------------------------------------


class TestFaultSpec:
    def test_parse_full_grammar(self):
        fs = faults.parse("gcs_read:step=13:kind=ioerror,"
                          "ckpt_shard:kind=corrupt,"
                          "host:step=20:kind=sigterm:once=1:times=3")
        assert [f.seam for f in fs] == ["gcs_read", "ckpt_shard", "host"]
        assert fs[0].step == 13 and fs[0].kind == "ioerror"
        assert fs[2].once and fs[2].times == 3

    def test_parse_rejects_unknowns_loudly(self):
        with pytest.raises(ValueError, match="unknown fault seam"):
            faults.parse("tpu_melt:step=1")
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse("gcs_read:kind=explode")
        with pytest.raises(ValueError, match="unknown fault option"):
            faults.parse("gcs_read:when=later")

    def test_removed_legacy_env_raises_with_spelling(self):
        """The pre-grammar aliases are gone — setting one must raise with
        the exact TPUFRAME_FAULTS spelling, never be silently ignored (a
        fault the operator thinks is armed but never fires turns every
        resilience proof downstream into a false pass)."""
        with pytest.raises(RuntimeError,
                           match=r"host:step=7:kind=crash:once=1"):
            faults.reset_from_env(
                {"TPUFRAME_FAULT_STEP": "7", "TPUFRAME_FAULT_ONCE": "1"})
        with pytest.raises(RuntimeError, match="TPUFRAME_FAULT_ONCE"):
            faults.reset_from_env({"TPUFRAME_FAULT_ONCE": "1"})
        # the modern spelling of the same fault still arms and still
        # honours the once=1 resumed-run drop
        reg = faults.reset_from_env(
            {"TPUFRAME_FAULTS": "host:step=7:kind=crash:once=1"})
        f = reg.faults[-1]
        assert (f.seam, f.kind, f.step, f.once) == ("host", "crash", 7, True)
        reg.set_resumed(True)
        assert reg.faults == []

    def test_ioerror_fires_once_per_times(self):
        reg = faults.FaultRegistry(faults.parse("gcs_read:times=2"))
        for _ in range(2):
            with pytest.raises(faults.InjectedFault):
                reg.fire("gcs_read")
        reg.fire("gcs_read")  # armed count spent — no-op

    def test_mangle_corrupt_and_torn(self):
        reg = faults.FaultRegistry(
            faults.parse("ckpt_shard:kind=corrupt,ckpt_shard:kind=torn"))
        data = bytes(range(64))
        bad = reg.mangle("ckpt_shard", data)
        assert len(bad) == len(data) and bad != data
        torn = reg.mangle("ckpt_shard", data)
        assert len(torn) == len(data) // 2
        assert reg.mangle("ckpt_shard", data) == data  # spent


# ---------------------------------------------------------------------------
# gcs layer: injected faults are retried, counters surface
# ---------------------------------------------------------------------------


def test_gcs_read_retries_injected_ioerrors(tmp_path, monkeypatch):
    p = tmp_path / "obj.bin"
    p.write_bytes(b"payload")
    monkeypatch.setenv("TPUFRAME_FAULTS", "gcs_read:kind=ioerror:times=2")
    faults.reset_from_env()
    metrics.reset_counters("retry.")
    assert gcs.read_bytes(str(p)) == b"payload"
    got = metrics.counters("retry.")
    assert got["retry.gcs_read.retries"] == 2
    assert got["retry.gcs_read.recovered"] == 1


def test_gcs_missing_file_not_retried(tmp_path):
    metrics.reset_counters("retry.")
    with pytest.raises(FileNotFoundError):
        gcs.read_bytes(str(tmp_path / "absent"))
    assert metrics.counters("retry.") == {}


# ---------------------------------------------------------------------------
# Checkpoint quarantine + walk-back
# ---------------------------------------------------------------------------


def _toy_state():
    return step_lib.TrainState.create(
        {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(())},
        optax.adam(1e-3))


def _save_two(tmp_path, state):
    ckpt.save(str(tmp_path), 1, state)
    ckpt.save(str(tmp_path), 2, state)


class TestQuarantineWalkBack:
    def test_corrupt_latest_shard_walks_back(self, tmp_path, capsys):
        state = _toy_state()
        _save_two(tmp_path, state)
        shard = next((tmp_path / "step_00000002").glob("*.npy"))
        raw = bytearray(shard.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        shard.write_bytes(bytes(raw))

        mgr = ckpt.CheckpointManager(str(tmp_path))
        step, tree = mgr.restore_latest(target=state)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(tree.params["w"]),
                                      np.asarray(state.params["w"]))
        assert (tmp_path / "step_00000002.corrupt").is_dir()
        assert not (tmp_path / "step_00000002").exists()
        assert "quarantined" in capsys.readouterr().out
        # quarantined dirs are invisible to latest_step forever after
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_torn_manifest_walks_back(self, tmp_path):
        state = _toy_state()
        _save_two(tmp_path, state)
        (tmp_path / "step_00000002" / "manifest.json").write_bytes(
            b'{"leaves": {"trunc')
        step, _ = ckpt.CheckpointManager(str(tmp_path)).restore_latest(
            target=state)
        assert step == 1
        assert (tmp_path / "step_00000002.corrupt").is_dir()

    def test_all_checkpoints_bad_returns_none(self, tmp_path):
        state = _toy_state()
        ckpt.save(str(tmp_path), 1, state)
        for shard in (tmp_path / "step_00000001").glob("*.npy"):
            shard.unlink()
        assert ckpt.CheckpointManager(str(tmp_path)).restore_latest(
            target=state) is None
        assert (tmp_path / "step_00000001.corrupt").is_dir()

    def test_structure_mismatch_still_raises(self, tmp_path):
        """A target/treedef disagreement is a config error, not storage
        corruption — walking back would mask it on every misconfigured
        job, so it must raise."""
        state = _toy_state()
        ckpt.save(str(tmp_path), 1, state)
        wrong_target = {"completely": jnp.zeros(3), "different": jnp.ones(2)}
        with pytest.raises(ValueError):
            ckpt.CheckpointManager(str(tmp_path)).restore_latest(
                target=wrong_target)
        assert (tmp_path / "step_00000001").is_dir()  # NOT quarantined

    def test_shard_fault_at_save_is_caught_at_restore(self, tmp_path,
                                                      monkeypatch):
        """kind=corrupt mangles the bytes written while the manifest CRC
        covers the clean bytes — exactly a storage-side flip, which the
        restore CRC check must catch and quarantine."""
        state = _toy_state()
        ckpt.save(str(tmp_path), 1, state)
        monkeypatch.setenv("TPUFRAME_FAULTS", "ckpt_shard:kind=corrupt")
        faults.reset_from_env()
        ckpt.save(str(tmp_path), 2, state)
        step, _ = ckpt.CheckpointManager(str(tmp_path)).restore_latest(
            target=state)
        assert step == 1
        assert (tmp_path / "step_00000002.corrupt").is_dir()


# ---------------------------------------------------------------------------
# Preemption contract: SIGTERM → checkpoint at step boundary → rc 14 → resume
# ---------------------------------------------------------------------------


def _smoke_cfg(tmp_path, **over):
    over.setdefault("distributed", False)
    over.setdefault("total_steps", 6)
    over.setdefault("log_every", 2)
    over.setdefault("eval_every", 1000)
    over.setdefault("ckpt_every", 10)  # periodic saves out of the way
    over.setdefault("global_batch", 16)
    over.setdefault("ckpt_dir", str(tmp_path / "ck"))
    return get_config("smoke").with_overrides(**over)


class TestPreemption:
    def test_guard_turns_sigterm_into_flag(self):
        with PreemptionGuard() as guard:
            assert not guard.requested
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.requested
            assert guard.signal_name == "SIGTERM"

    def test_second_sigint_escalates(self):
        guard = PreemptionGuard().install()
        try:
            os.kill(os.getpid(), signal.SIGINT)
            assert guard.requested
            with pytest.raises(KeyboardInterrupt):
                os.kill(os.getpid(), signal.SIGINT)
        finally:
            guard.uninstall()

    def test_second_sigterm_escalates_to_kill(self, tmp_path):
        """A second SIGTERM (the supervisor's kill-after-grace) must
        actually terminate a wedged run — re-delivered with the guard
        uninstalled, so the default action fires.  Subprocess: the
        escalation kills the whole process by design."""
        import subprocess
        import sys
        import textwrap

        script = textwrap.dedent("""
            import os, signal
            from tpuframe.resilience.preempt import PreemptionGuard
            g = PreemptionGuard().install()
            os.kill(os.getpid(), signal.SIGTERM)
            assert g.requested and g.signal_name == "SIGTERM"
            os.kill(os.getpid(), signal.SIGTERM)  # escalation: no return
            print("SHIELDED")  # must be unreachable
        """)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == -signal.SIGTERM, (out.returncode,
                                                   out.stderr[-800:])
        assert "SHIELDED" not in out.stdout

    def test_reassert_takes_signal_back(self):
        """jax.distributed's preemption notifier steals SIGTERM after the
        guard installs; reassert() must reclaim it (regression: preemption
        silently disabled under the local fake cluster)."""
        guard = PreemptionGuard().install()
        try:
            signal.signal(signal.SIGTERM, lambda s, f: None)  # the thief
            guard.reassert()
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.requested
        finally:
            guard.uninstall()

    def test_sigterm_mid_run_checkpoints_and_exits_14(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("TPUFRAME_FAULTS", "host:step=3:kind=sigterm")
        with pytest.raises(SystemExit) as ei:
            train_mod.train(_smoke_cfg(tmp_path))
        assert ei.value.code == RC_PREEMPTED
        # the final checkpoint is COMMITTED at the preempted boundary
        assert (tmp_path / "ck" / "step_00000003" / "COMMIT").exists()
        assert ckpt.latest_step(str(tmp_path / "ck")) == 3

        # ...and a clean resume finishes the job from there
        monkeypatch.delenv("TPUFRAME_FAULTS")
        metrics_out = train_mod.train(_smoke_cfg(tmp_path))
        assert metrics_out["step"] == 6

    def test_supervisor_resumes_preempted_job_to_completion(self, tmp_path,
                                                            monkeypatch):
        """End-to-end contract: preemption costs the supervisor nothing —
        rc 14 relaunches immediately with zero relaunch budget."""
        monkeypatch.setenv("TPUFRAME_FAULTS", "host:step=3:kind=sigterm")
        out = {}

        def run_once():
            try:
                out.update(train_mod.train(_smoke_cfg(tmp_path)))
                return 0
            except SystemExit as e:
                return int(e.code)

        msgs = []
        rc = run_with_relaunch(run_once, 0, log=msgs.append,
                               sleep=lambda s: None)
        assert rc == 0
        assert out["step"] == 6
        assert any("preempted" in m for m in msgs)


# ---------------------------------------------------------------------------
# Supervisor hardening: backoff, crash loops, budget refresh
# ---------------------------------------------------------------------------


class TestSupervisor:
    def test_backoff_doubles_with_cap(self):
        sleeps = []

        def run_once():
            return 1

        rc = run_with_relaunch(
            run_once, 5, log=lambda m: None, sleep=sleeps.append,
            backoff_base_s=1.0, backoff_max_s=4.0,
            rng=_FixedRng())  # uniform() -> upper bound, i.e. delay itself
        assert rc == 1
        np.testing.assert_allclose(sleeps, [1.0, 2.0, 4.0, 4.0, 4.0])

    def test_preempted_rc_skips_backoff_and_budget(self):
        rcs = iter([RC_PREEMPTED, RC_PREEMPTED, 0])
        sleeps = []
        rc = run_with_relaunch(lambda: next(rcs), 0, log=lambda m: None,
                               sleep=sleeps.append)
        assert rc == 0
        assert sleeps == []  # no backoff, no budget consumed

    def test_crash_loop_without_progress_gives_up_early(self):
        calls = {"n": 0}

        def run_once():
            calls["n"] += 1
            return 42

        msgs = []
        rc = run_with_relaunch(run_once, 100, log=msgs.append,
                               sleep=lambda s: None, progress=lambda: 5,
                               max_stalled=2)
        assert rc == 42
        assert calls["n"] == 3  # initial + 2 stalled relaunches, not 101
        assert any("crash loop" in m for m in msgs)

    def test_checkpoint_progress_refreshes_budget(self):
        state = {"n": 0, "step": 0}

        def run_once():
            state["n"] += 1
            state["step"] += 10  # every attempt commits a new checkpoint
            return 13 if state["n"] < 6 else 0

        msgs = []
        rc = run_with_relaunch(run_once, 1, log=msgs.append,
                               sleep=lambda s: None,
                               progress=lambda: state["step"])
        # budget of ONE relaunch survives five failures because each one
        # made checkpoint progress
        assert rc == 0
        assert state["n"] == 6
        assert any("budget refreshed" in m for m in msgs)


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------


def test_heartbeat_survives_broken_on_stall_callback(caplog):
    import logging

    def bad_callback(idle):
        raise RuntimeError("observer bug")

    hb = Heartbeat(timeout_s=0.05, poll_s=0.01, on_stall=bad_callback)
    with caplog.at_level(logging.ERROR, logger="tpuframe.obs.heartbeat"):
        hb.start()
        # `stalled` flips just before the callback runs, so poll for the
        # logged traceback itself, not the flag.
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and not any(
                "on_stall callback raised" in r.message
                for r in caplog.records):
            time.sleep(0.01)
    assert hb.stalled
    assert hb._thread.is_alive()  # the watchdog outlived the bad callback
    assert any("on_stall callback raised" in r.message
               for r in caplog.records)
    hb.stop()


def test_metrics_counters_roundtrip():
    metrics.reset_counters()
    metrics.bump("retry.x.retries")
    metrics.bump("retry.x.retries", 2)
    metrics.bump("other.thing")
    assert metrics.counters("retry.") == {"retry.x.retries": 3}
    assert metrics.counters()["other.thing"] == 1
    metrics.reset_counters("retry.")
    assert metrics.counters("retry.") == {}
    assert metrics.counters()["other.thing"] == 1
    metrics.reset_counters()


def test_retry_counters_reach_train_metrics(tmp_path, monkeypatch):
    """Acceptance demo (a): injected gcs_read IOErrors are retried and the
    run completes with retry counts in the returned metrics."""
    monkeypatch.setenv("TPUFRAME_FAULTS", "gcs_read:kind=ioerror:times=2")
    metrics.reset_counters("retry.")
    out = train_mod.train(_smoke_cfg(tmp_path, total_steps=4, ckpt_every=2))
    assert out["step"] == 4
    assert out.get("retry.gcs_read.retries", 0) == 2
    assert out.get("retry.gcs_read.recovered", 0) == 1
