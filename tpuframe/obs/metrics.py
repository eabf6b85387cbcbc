"""Metrics & throughput logging.

Reference parity (SURVEY.md §5.5): rank-0-gated prints + allreduce-averaged
scalars; the north-star metric is images/sec/chip [B:2], so the rate meter is
first-class.  Output is stdout lines + a JSONL file (local or gs://-style via
append-on-host then upload at close).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path

import jax

# ---------------------------------------------------------------------------
# Process-wide event counters.  The resilience layer bumps these from retry
# loops (``retry.gcs_read.retries`` etc.), which may run in checkpoint/data
# threads — hence the lock.  Deliberately not jax-aware: counters are
# per-host facts and must work before any backend exists.
# ---------------------------------------------------------------------------

_counters: dict[str, int] = {}
_counters_lock = threading.Lock()
# Counters kept elsewhere (on the device, by a jitted step) and fetched
# only when asked for by name: ``{prefix: fn() -> {name: int}}``.
_sources: dict = {}


def register_source(prefix: str, fn) -> None:
    """Counters under ``prefix`` come from ``fn()`` when :func:`counters`
    is read with a prefix that falls under it, and cost nothing until
    then.  A read without a prefix (the flight recorder's dump, the
    exporter's scrape, ``run_end``) never calls ``fn``: those run on
    threads and at moments at which a device transfer may block."""
    _sources[prefix] = fn


def bump(name: str, n: int = 1) -> None:
    """Increment the process-wide counter ``name`` by ``n``.

    Callers are retry loops and cache listeners mid-recovery: this must
    be safe at any point in the process lifecycle — before any logger
    exists, after ``MetricLogger.close()``, during interpreter teardown —
    and never raise back into the instrumented seam."""
    try:
        with _counters_lock:
            _counters[name] = _counters.get(name, 0) + int(n)
    except Exception:  # noqa: BLE001 — teardown / bad n; drop the bump
        pass


def counters(prefix: str | None = None) -> dict[str, int]:
    """Snapshot of counters, optionally filtered to ``prefix``."""
    with _counters_lock:
        out = dict(_counters)
    for own, fn in list(_sources.items()):
        if prefix is not None and prefix.startswith(own):
            out.update(fn())
    return {k: v for k, v in out.items()
            if prefix is None or k.startswith(prefix)}


def reset_counters(prefix: str | None = None) -> None:
    for own in [p for p in _sources
                if prefix is None or p.startswith(prefix)]:
        del _sources[own]
    with _counters_lock:
        if prefix is None:
            _counters.clear()
        else:
            for k in [k for k in _counters if k.startswith(prefix)]:
                del _counters[k]


def counters_reset(prefix: str | None = None) -> None:
    """Test-friendly alias for :func:`reset_counters` (the obs v2 API
    name); both clear the process-wide counter table."""
    reset_counters(prefix)


class RateMeter:
    """Examples/sec with warmup exclusion (first N steps are compile+cache)
    and pause support so eval/checkpoint wall-clock doesn't deflate the
    training-throughput number (the north-star metric, [B:2])."""

    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = warmup_steps
        self._count = 0
        self._examples = 0
        self._t0: float | None = None
        self._excluded = 0.0

    def update(self, batch_examples: int) -> None:
        self._count += 1
        if self._count == self.warmup_steps:
            self._t0 = time.perf_counter()
            self._examples = 0
            self._excluded = 0.0
        elif self._count > self.warmup_steps:
            self._examples += batch_examples

    @contextlib.contextmanager
    def paused(self):
        """Exclude the wrapped wall-clock (eval passes, blocking saves)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._excluded += time.perf_counter() - t0

    def rate(self) -> float | None:
        """examples/sec since warmup, None until measurable."""
        if self._t0 is None or self._examples == 0:
            return None
        dt = time.perf_counter() - self._t0 - self._excluded
        return self._examples / dt if dt > 0 else None

    def per_chip(self) -> float | None:
        r = self.rate()
        return r / jax.device_count() if r is not None else None


class MetricLogger:
    """Rank-0-gated structured logging: stdout + JSONL (local file appended
    live; ``gs://`` paths uploaded as periodic segment objects so a crash
    loses at most one flush window and resumes never overwrite history)."""

    def __init__(self, log_file: str | None = None, *, stdout: bool = True,
                 gcs_flush_every: int = 50, tb_dir: str | None = None):
        from tpuframe.data import gcs

        self.primary = jax.process_index() == 0
        self.stdout = stdout
        self._fh = None
        self._gcs_path: str | None = None
        self._gcs_buf: list[str] = []
        self._gcs_segment = 0
        self._gcs_flush_every = gcs_flush_every
        self._tb = None
        if self.primary and tb_dir:
            # TensorBoard event-file sink (SURVEY.md §5.5) — local or gs://.
            from tpuframe.obs.tensorboard import SummaryWriter

            self._tb = SummaryWriter(tb_dir)
        if self.primary and log_file:
            if gcs.is_gcs_path(log_file):
                self._gcs_path = log_file
                # Unique run suffix: resumed runs append new segments instead
                # of overwriting the previous run's log at the same path.
                self._gcs_run = int(time.time())
            else:
                Path(log_file).parent.mkdir(parents=True, exist_ok=True)
                self._fh = open(log_file, "a", buffering=1)

    def log(self, step: int, metrics: dict, *, prefix: str = "train") -> None:
        if not self.primary:
            return
        clean = {k: (float(v) if hasattr(v, "item") or isinstance(v, (int, float))
                     else v) for k, v in metrics.items()}
        record = {"step": step, "prefix": prefix, "time": time.time(), **clean}
        line = json.dumps(record)
        if self._tb is not None:
            self._tb.add_scalars(step, clean, prefix=prefix)
        if self._fh:
            self._fh.write(line + "\n")
        elif self._gcs_path is not None:
            self._gcs_buf.append(line)
            if len(self._gcs_buf) >= self._gcs_flush_every:
                self._flush_gcs()
        if self.stdout:
            body = " ".join(f"{k}={v:.5g}" if isinstance(v, float) else f"{k}={v}"
                            for k, v in clean.items())
            print(f"[{prefix} {step}] {body}", flush=True)

    def _flush_gcs(self) -> None:
        """Write the buffered lines as a new segment object
        (``<path>.<runid>.<seg>``) so crashes lose at most one window and
        resumed runs never clobber earlier segments; readers concatenate."""
        if not self._gcs_buf:
            return
        from tpuframe.data import gcs

        seg_path = f"{self._gcs_path}.{self._gcs_run}.{self._gcs_segment:04d}"
        gcs.write_bytes(seg_path, ("\n".join(self._gcs_buf) + "\n").encode())
        self._gcs_segment += 1
        self._gcs_buf = []

    def close(self) -> None:
        if self._tb is not None:
            self._tb.close()
            self._tb = None
        if self._fh:
            self._fh.close()
            self._fh = None
        if self._gcs_path is not None:
            self._flush_gcs()
