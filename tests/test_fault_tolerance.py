"""Fault injection + elastic resume + SPMD-divergence checks
(SURVEY.md §5.3: failure = job death + resume from checkpoint; §5.2)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tpuframe.launch import LocalCluster
from tpuframe.obs import spmd_check


def _run_train(tmp_path, extra_env=None, total_steps=20):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": env.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4",
    })
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "tpuframe.train", "--config", "smoke",
         "--set", f"total_steps={total_steps}", "--set", "ckpt_every=5",
         "--set", "log_every=5", "--set", "eval_every=1000",
         "--set", "global_batch=16", "--ckpt-dir", str(tmp_path / "ck")],
        env=env, capture_output=True, text=True, timeout=500)


@pytest.mark.slow
def test_crash_and_resume(tmp_path):
    """Hard-kill (os._exit, no cleanup) at step 13; the restarted job must
    resume from the last committed checkpoint (step 10) and finish — the
    slice-restart recovery model (SURVEY.md §5.3)."""
    crashed = _run_train(tmp_path, {"TPUFRAME_FAULTS": "host:step=13:kind=crash"})
    assert crashed.returncode == 42, crashed.stderr[-1500:]
    assert "FAULT INJECTION" in crashed.stdout
    # checkpoints 5 and 10 committed; nothing at 13
    ck = tmp_path / "ck"
    committed = sorted(p.name for p in ck.iterdir() if p.is_dir())
    assert "step_00000010" in committed

    resumed = _run_train(tmp_path)
    assert resumed.returncode == 0, resumed.stderr[-1500:]
    assert "resumed from step 10" in resumed.stdout
    assert "[train 20]" in resumed.stdout


@pytest.mark.slow
def test_resumed_loss_matches_straight_run(tmp_path):
    straight = _run_train(tmp_path / "a")
    assert straight.returncode == 0, straight.stderr[-1500:]
    crashed = _run_train(tmp_path / "b", {"TPUFRAME_FAULTS": "host:step=13:kind=crash"})
    assert crashed.returncode == 42
    resumed = _run_train(tmp_path / "b")
    assert resumed.returncode == 0, resumed.stderr[-1500:]

    def final_loss(out):
        line = next(l for l in out.stdout.splitlines() if "[train 20]" in l)
        return float(line.split("loss=")[1].split()[0])

    np.testing.assert_allclose(final_loss(resumed), final_loss(straight),
                               rtol=1e-4)


def test_spmd_check_single_process_noop():
    spmd_check.assert_uniform_across_hosts("tag", b"anything")  # must not raise


def test_digest_stable():
    a = spmd_check.digest("payload")
    b = spmd_check.digest(b"payload")
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, spmd_check.digest("payload2"))


@pytest.mark.slow
def test_spmd_divergence_detected_across_hosts():
    """2-host cluster: uniform payload passes; a host-dependent payload is
    caught before any training collective would hang."""
    script = textwrap.dedent("""
        import jax
        from tpuframe.parallel import bootstrap
        from tpuframe.obs import spmd_check
        bootstrap.initialize()
        spmd_check.assert_uniform_across_hosts("ok", b"same-on-all-hosts")
        try:
            spmd_check.assert_uniform_across_hosts(
                "drift", f"host-{jax.process_index()}".encode())
        except RuntimeError as e:
            assert "divergence" in str(e)
            print("CAUGHT")
        else:
            raise SystemExit("divergence not detected")
    """)
    results = LocalCluster(2, 1, timeout=300).launch(
        [sys.executable, "-c", script])
    assert all("CAUGHT" in r.stdout for r in results)


@pytest.mark.slow
def test_stall_becomes_clean_abort(tmp_path):
    """Collective-timeout surfacing (SURVEY.md §5.3): wedge one rank mid-run;
    every rank's heartbeat watchdog must turn the resulting pod-wide stall
    into a clean exit-13 (not an indefinite hang), leaving the last committed
    checkpoint for auto-resume.  Rank 1 stalls in its host loop; rank 0
    stalls inside the collective waiting for it — both paths must abort."""
    with pytest.raises(RuntimeError) as excinfo:
        LocalCluster(
            2, 2, timeout=400,
            extra_env={
                "TPUFRAME_HANG_STEP": "3",        # only rank 1 hangs
                "TPUFRAME_HANG_RANK": "1",
                "TPUFRAME_STALL_TIMEOUT_S": "20",
            },
        ).launch([
            sys.executable, "-m", "tpuframe.train", "--config", "smoke",
            "--set", "total_steps=30", "--set", "log_every=5",
            "--set", "eval_every=1000", "--set", "global_batch=16",
            "--set", "ckpt_every=2", "--ckpt-dir", str(tmp_path / "ck"),
        ])
    msg = str(excinfo.value)
    assert "exit 13" in msg, msg
    assert "STALL" in msg, msg
    # a committed checkpoint exists for the restart to resume from
    committed = sorted(p.name for p in (tmp_path / "ck").iterdir()
                       if p.is_dir())
    assert any(n.startswith("step_") for n in committed), committed


@pytest.mark.slow
def test_spmd_check_enabled_in_harness():
    """TPUFRAME_CHECK_SPMD=1 through the real train.py on 2 hosts."""
    results = LocalCluster(
        2, 2, timeout=500,
        extra_env={"TPUFRAME_CHECK_SPMD": "1"},
    ).launch([
        sys.executable, "-m", "tpuframe.train", "--config", "smoke",
        "--set", "total_steps=4", "--set", "log_every=2",
        "--set", "eval_every=100", "--set", "global_batch=16",
    ])
    assert "done in" in results[0].stdout
