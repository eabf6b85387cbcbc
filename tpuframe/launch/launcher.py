"""Launchers — L5 of the layer map: the ``horovodrun`` replacement.

Reference launch path (SURVEY.md §4.2): ``horovodrun -np 32 -H a:8,... python
train.py`` → mpirun/ssh spawns one process per GPU.  TPU-native SPMD launch
is simpler and different in shape: ONE process per *host*, each seeing the
host's chips, every host running the SAME binary; rendezvous happens through
``jax.distributed.initialize`` (GRPC coordinator), not MPI.

Two launchers:

  * :class:`SliceLauncher` — production: fans the command out to every
    TPU-VM worker over ``gcloud ... ssh --worker=all`` (built by
    tpuframe.launch.provision); each worker autodetects its process id from
    the TPU metadata (``TPUFRAME_MULTIHOST=1``).

  * :class:`LocalCluster` — the CI stand-in (SURVEY.md §7 "fake cluster"):
    spawns N *local* processes, each a separate jax runtime with K forced
    host CPU devices, wired together with TPUFRAME_COORDINATOR/_PROCESS_ID
    env vars consumed by tpuframe.parallel.bootstrap.  Multi-host semantics
    (process_count > 1, cross-host collectives, per-host data sharding) are
    exercised for real, with zero TPUs.
"""

from __future__ import annotations

import random
import re
import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field

from tpuframe import elastic
from tpuframe.launch.provision import SliceConfig
from tpuframe.obs import exporter as exporter_lib
from tpuframe.resilience.preempt import RC_PREEMPTED
from tpuframe.utils import compile_cache


def _free_port() -> int:
    # Local ephemeral-port probe (bind on loopback, never fleet traffic)
    # — no retry/backoff semantics to bypass.
    with socket.socket() as s:  # tf-lint: ok[TF118]
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class CompletedProcess:
    process_id: int
    returncode: int
    stdout: str
    stderr: str


@dataclass
class LocalCluster:
    """Spawn ``num_processes`` local SPMD processes (CPU backend).

    ``devices_per_process`` forced host devices each → a virtual
    ``num_processes × devices_per_process``-chip cluster.
    """

    num_processes: int = 2
    devices_per_process: int = 4
    timeout: float = 600.0
    extra_env: dict[str, str] = field(default_factory=dict)

    def launch(self, argv: list[str]) -> list[CompletedProcess]:
        """Run ``argv`` (e.g. ``[sys.executable, "-m", "tpuframe.train", ...]``)
        once per process; block until all exit.  Raises ``RuntimeError`` if
        any process fails — with every rank's tail, since SPMD failures often
        only explain themselves on one rank."""
        port = _free_port()
        procs = []
        for pid in range(self.num_processes):
            env = dict(os.environ)
            env.update({
                # the fake cluster runs on the CPU backend
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                              f" --xla_force_host_platform_device_count="
                              f"{self.devices_per_process}"),
                "TPUFRAME_COORDINATOR": f"127.0.0.1:{port}",
                "TPUFRAME_NUM_PROCESSES": str(self.num_processes),
                "TPUFRAME_PROCESS_ID": str(pid),
            })
            # Pin all ranks (and any relaunch of this cluster) to one
            # persistent compilation cache so warm restarts skip the
            # recompile (the standard jax variable, which
            # utils/compile_cache honours).  An operator's setting wins.
            env.setdefault("JAX_COMPILATION_CACHE_DIR",
                           compile_cache.default_cache_dir())
            env.update(self.extra_env)
            procs.append(subprocess.Popen(
                argv, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))

        results = []
        for pid, p in enumerate(procs):
            try:
                out, err = p.communicate(timeout=self.timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise RuntimeError(
                    f"local cluster rank {pid} timed out after {self.timeout}s")
            results.append(CompletedProcess(pid, p.returncode, out, err))

        failures = [r for r in results if r.returncode != 0]
        if failures:
            detail = "\n".join(
                f"--- rank {r.process_id} (exit {r.returncode}) ---\n"
                f"{r.stderr[-2000:]}" for r in failures)
            raise RuntimeError(f"local cluster failed:\n{detail}")
        return results


@dataclass
class SliceLauncher:
    """Fan a command out to every worker of a TPU-VM slice.

    ``dry_run=True`` returns the argv lists instead of executing — the
    testable surface in environments without gcloud credentials."""

    slice_cfg: SliceConfig
    dry_run: bool = False

    def launch(self, command: str, env: dict[str, str] | None = None):
        full_env = {"TPUFRAME_MULTIHOST": "1", **(env or {})}
        cmd = self.slice_cfg.ssh_cmd(command, worker="all", env=full_env)
        if self.dry_run:
            return cmd
        return subprocess.run(cmd, check=True)


def run_with_relaunch(run_once, relaunches: int, *, log=print,
                      progress=None, backoff_base_s: float | None = None,
                      backoff_max_s: float | None = None,
                      max_stalled: int | None = None,
                      sleep=time.sleep, rng: random.Random | None = None
                      ) -> int:
    """Supervise a job through slice-restart recovery (SURVEY.md §5.3).

    The failure model: jobs that stall or lose a host exit nonzero (the
    harness's stall watchdog exits 13 precisely so a supervisor restarts
    it), and the restarted job auto-resumes from the latest committed
    checkpoint — the TPU-native replacement for hvd.elastic's in-place
    re-rendezvous.  ``run_once() -> int`` is re-invoked until it returns 0
    or ``relaunches`` restarts are spent.

    Hardened semantics (docs/DESIGN.md "Failure model & resilience"):

      * rc 14 (:data:`RC_PREEMPTED`) is *cooperative*: the job already
        committed a final checkpoint, so it relaunches immediately —
        no backoff and no charge against the relaunch budget.
      * Crashes back off exponentially with jitter before each relaunch
        (base ``TPUFRAME_RELAUNCH_BACKOFF_S`` [1s], doubling to
        ``backoff_max_s`` [60s]) so a hard-down dependency is not hammered.
      * Crash-loop detection: when ``progress() -> int|None`` (typically
        ``latest_step`` on the job's checkpoint dir) shows no advance
        across ``max_stalled`` (``TPUFRAME_RELAUNCH_MAX_STALLED`` [3])
        consecutive relaunches, the supervisor gives up early — a job
        dying at the same step every time will not burn a day of budget.
      * Any checkpoint progress *refreshes* the budget: attempts, the
        stall counter and the backoff all reset, so a long job that fails
        occasionally-but-productively can keep going indefinitely.
    """
    if backoff_base_s is None:
        backoff_base_s = float(
            os.environ.get("TPUFRAME_RELAUNCH_BACKOFF_S", "1.0"))
    if backoff_max_s is None:
        backoff_max_s = 60.0
    if max_stalled is None:
        max_stalled = int(
            os.environ.get("TPUFRAME_RELAUNCH_MAX_STALLED", "3"))
    rng = rng or random.Random()
    attempt = 0
    stalled = 0
    delay = backoff_base_s
    last_progress = progress() if progress is not None else None
    # Attempt stitching for the structured event log (obs/events.py): every
    # (re)launch — cooperative rc-14 resumes included — gets the next serial
    # so one events.<host>.jsonl reconstructs the full supervised lifecycle.
    # Env contract, not an import: run_once children inherit os.environ.
    attempt_serial = int(os.environ.get("TPUFRAME_ATTEMPT", "0") or "0")
    # Supervisor's own telemetry (obs/exporter.py): bound one port above
    # the child's (``port_offset=1``) so both can serve on one host.
    # Relaunch accounting is exactly what a pager wants from a supervisor:
    # attempts spent, last exit code, crash-loop stall count.
    exporter = exporter_lib.start_from_env(port_offset=1)

    def _export(rc=None):
        if exporter is None:
            return
        exporter.set_gauge("tpuframe_supervisor_attempts", attempt)
        exporter.set_gauge("tpuframe_supervisor_attempt_serial",
                           attempt_serial)
        exporter.set_gauge("tpuframe_supervisor_stalled_relaunches",
                           stalled)
        if rc is not None:
            exporter.set_gauge("tpuframe_supervisor_last_rc", rc)
        exporter.flush()

    while True:
        os.environ["TPUFRAME_ATTEMPT"] = str(attempt_serial)
        attempt_serial += 1
        _export()
        rc = run_once()
        _export(rc)
        if rc == 0:
            return rc
        if rc == RC_PREEMPTED:
            log(f"[tpuframe.launch] job preempted (rc={rc}); relaunching "
                f"immediately (checkpoint committed, budget untouched)")
            continue
        if progress is not None:
            now = progress()
            if now is not None and (last_progress is None
                                    or now > last_progress):
                if attempt or stalled:
                    log(f"[tpuframe.launch] checkpoint progress "
                        f"(latest step {now}) — relaunch budget refreshed")
                last_progress = now
                attempt = 0
                stalled = 0
                delay = backoff_base_s
            else:
                stalled += 1
                if stalled > max_stalled:
                    log(f"[tpuframe.launch] crash loop: no checkpoint "
                        f"progress across {stalled} relaunches — giving up; "
                        f"last rc={rc}")
                    return rc
        if attempt >= relaunches:
            if relaunches > 0:
                log(f"[tpuframe.launch] giving up after {attempt} "
                    f"relaunch(es); last rc={rc}")
            return rc
        attempt += 1
        log(f"[tpuframe.launch] job exited rc={rc}; relaunch "
            f"{attempt}/{relaunches} in {delay:.1f}s "
            f"(resume from latest checkpoint)")
        sleep(delay * rng.uniform(0.5, 1.0))
        delay = min(backoff_max_s, delay * 2.0)


def _progress_probe(cmd: list[str], *, log=print):
    """A ``progress()`` callable for :func:`run_with_relaunch`, watching the
    job's checkpoint directory when one is discoverable from its argv
    (``--ckpt-dir X`` or ``--ckpt-dir=X``).  None when there isn't one —
    crash-loop detection simply stays off.

    Elastic tolerance: under a ``TPUFRAME_ELASTIC`` schedule consecutive
    attempts run at DIFFERENT world sizes, so the directory accumulates
    committed checkpoints written at several n.  Progress is measured in
    steps, which are world-size invariant — a commit from any n counts,
    and a manifest whose ``world`` metadata is absent (pre-elastic),
    foreign, or unreadable must never zero the budget refresh.  The world
    peek below is therefore strictly best-effort visibility: it logs the
    n→n′ transition supervisor-side and feeds nothing into the progress
    value."""
    ckpt_dir = None
    for i, arg in enumerate(cmd):
        if arg == "--ckpt-dir" and i + 1 < len(cmd):
            ckpt_dir = cmd[i + 1]
        elif arg.startswith("--ckpt-dir="):
            ckpt_dir = arg.split("=", 1)[1]
    if not ckpt_dir:
        return None
    seen_world: list[int] = []

    def probe():
        from tpuframe.ckpt.checkpoint import (committed_world,
                                              in_flight_step, latest_step)

        try:
            # In-flight saves count: a job preempted mid-upload advanced
            # past its last COMMIT, and the relaunch will either finish
            # the commit or retrain those few steps — either way it is
            # not a crash loop, and the budget must not be charged as
            # one.
            marks = [s for s in (latest_step(ckpt_dir),
                                 in_flight_step(ckpt_dir))
                     if s is not None]
            world = committed_world(ckpt_dir)
            devices = int(world["devices"]) if world else 0
            if devices > 0:
                if seen_world and seen_world[-1] != devices:
                    log(f"[tpuframe.launch] checkpoint world resized "
                        f"{seen_world[-1]}→{devices} devices (committed "
                        f"step {world.get('step')}) — progress accounting "
                        f"unaffected, steps are world-size invariant")
                if not seen_world or seen_world[-1] != devices:
                    seen_world.append(devices)
            return max(marks) if marks else None
        except Exception:  # noqa: BLE001 — a flaky probe must not kill the
            # supervisor; "unknown" just means no budget refresh this round.
            return None

    return probe


def main(argv: list[str] | None = None) -> int:
    """CLI::

        # fake cluster (CI): 2 hosts x 4 devices running the smoke config
        python -m tpuframe.launch local --nprocs 2 --devices 4 -- \\
            python -m tpuframe.train --config smoke

        # real slice: provision scripts + SPMD fan-out
        python -m tpuframe.launch provision --name pod --accelerator v4-32 \\
            --out launch_scripts/
        python -m tpuframe.launch slice --name pod --accelerator v4-32 -- \\
            python -m tpuframe.train --config imagenet_resnet50_pod
    """
    import argparse

    p = argparse.ArgumentParser(prog="tpuframe.launch", description=main.__doc__)
    sub = p.add_subparsers(dest="mode", required=True)

    lp = sub.add_parser("local", help="spawn a local multi-process fake cluster")
    lp.add_argument("--nprocs", type=int, default=2)
    lp.add_argument("--devices", type=int, default=4,
                    help="forced host devices per process")
    lp.add_argument("--relaunch", type=int, default=0, metavar="N",
                    help="restart a failed job up to N times (auto-resume)")
    lp.add_argument("cmd", nargs=argparse.REMAINDER)

    pp = sub.add_parser("provision", help="emit gcloud provisioning scripts")
    pp.add_argument("--name", required=True)
    pp.add_argument("--zone", default="us-central2-b")
    pp.add_argument("--accelerator", default="v4-32")
    pp.add_argument("--out", default="launch_scripts")

    sp = sub.add_parser("slice", help="run a command on every slice worker")
    sp.add_argument("--name", required=True)
    sp.add_argument("--zone", default="us-central2-b")
    sp.add_argument("--accelerator", default="v4-32")
    sp.add_argument("--dry-run", action="store_true")
    sp.add_argument("--relaunch", type=int, default=0, metavar="N",
                    help="restart a failed job up to N times (auto-resume)")
    sp.add_argument("cmd", nargs=argparse.REMAINDER)

    args = p.parse_args(argv)

    if args.mode == "local":
        cmd = [c for c in args.cmd if c != "--"]
        schedule = elastic.schedule_from_env()

        def run_once() -> int:
            # Elastic membership plan: each supervisor attempt may run at
            # a different TOTAL device count (TPUFRAME_ELASTIC="8,4,8" —
            # shrink after the first membership change, grow back after
            # the second).  The cluster is rebuilt per attempt, so the
            # relaunch IS the re-rendezvous; restore reshards the state.
            devices = args.devices
            if schedule:
                attempt = int(os.environ.get("TPUFRAME_ATTEMPT", "0")
                              or "0")
                n_total = elastic.world_for_attempt(attempt, schedule)
                if n_total % args.nprocs:
                    print(f"[tpuframe.launch] TPUFRAME_ELASTIC leg "
                          f"{n_total} is not divisible by --nprocs "
                          f"{args.nprocs}")
                    return 2
                devices = n_total // args.nprocs
                print(f"[tpuframe.launch] elastic attempt {attempt}: "
                      f"world {n_total} devices ({args.nprocs} proc × "
                      f"{devices} dev)")
            try:
                results = LocalCluster(args.nprocs, devices).launch(cmd)
            except RuntimeError as e:
                print(f"[tpuframe.launch] {e}")
                # preserve the failure model's exit codes (13 = stall
                # abort, 42-class = crash injection): surface the first
                # failing rank's rc rather than flattening to 1.
                m = re.search(r"exit (\d+)", str(e))
                return int(m.group(1)) if m else 1
            for r in results:
                prefix = f"[rank {r.process_id}] "
                for line in r.stdout.strip().splitlines():
                    print(prefix + line)
            return 0

        return run_with_relaunch(run_once, args.relaunch,
                                 progress=_progress_probe(cmd))

    cfg = SliceConfig(name=args.name, zone=args.zone,
                      accelerator=args.accelerator)
    if args.mode == "provision":
        from tpuframe.launch.provision import emit_scripts

        paths = emit_scripts(cfg, args.out)
        for name, path in paths.items():
            print(f"wrote {path}")
        return 0

    cmd = " ".join(c for c in args.cmd if c != "--")
    launcher = SliceLauncher(cfg, dry_run=args.dry_run)
    if args.dry_run:
        print(" ".join(launcher.launch(cmd)))
        return 0

    def run_once() -> int:
        try:
            launcher.launch(cmd)
        except subprocess.CalledProcessError as e:
            return e.returncode or 1
        return 0

    return run_with_relaunch(run_once, args.relaunch,
                             progress=_progress_probe(args.cmd))


if __name__ == "__main__":
    raise SystemExit(main())
