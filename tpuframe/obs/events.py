"""Structured run-event log — the joinable record of what a run *did*.

The reference's observability surface was the Horovod timeline plus rank-0
throughput prints (SURVEY.md §5.1/§5.5); PR 1-3 replaced the timeline with
XLA profiler hooks and grew counters, but steps, restarts, retries, stalls
and compile events still lived in unjoinable stdout lines.  This module is
the structured layer underneath all of them: a process-wide, thread-safe
JSONL writer, one file per host (``events.<host>.jsonl``), every record
carrying a common envelope so one directory of files reconstructs the full
lifecycle of a run — including supervised relaunches, which are stitched
together by the ``attempt`` field the supervisor increments
(``launch/launcher.py:run_with_relaunch`` → ``TPUFRAME_ATTEMPT``).

Record envelope (every line)::

    {"schema": 2, "type": "<event type>", "t": <unix seconds>,
     "host": "<hostname>", "proc": <process index>, "attempt": <int>,
     ...type-specific fields}

Schema history: v2 added the ``input`` goodput bucket (``run_end``'s
``goodput.buckets``) and the optional ``input_wait_ms``/``block_ms``
fields on ``step``/``ckpt_save``.  v1 logs stay readable — the new
fields are additive, so the validator accepts every version in
``ACCEPTED_SCHEMAS`` and the analyzer treats the absent fields as zero.

Event types (see ``REQUIRED_FIELDS`` for the per-type contract):

  ============== ========================================================
  run_start      run manifest: config name+hash, mesh/topology, jax
                 version, tune-DB fingerprint, TPUFRAME_XLA_OPTS,
                 resume step, device_kind and the generation MFU is
                 priced at with its source (device|env|assumed)
  step           step index, host wall ms, loss, examples processed
  compile        a compilation observed (first-step wall, or a
                 persistent-cache hit/miss from utils/compile_cache)
  ckpt_save      checkpoint written (step, ms, async?)
  ckpt_restore   checkpoint restored (step, ms)
  retry          a retry-policy attempt fired (op, outcome)
  fault_injected a TPUFRAME_FAULTS seam fired (seam, kind, step)
  stall          heartbeat watchdog fired (last_step, idle_s)
  preempt        SIGTERM/SIGINT preemption observed (signal[, step])
  devmem         HBM telemetry sample (per-device memory_stats)
  remat_policy   rematerialization policy chosen for the step program
                 (policy name, resolution source, predicted bytes)
  weight_update  weight-update sharding mode chosen for the step program
                 (mode replicated|zero1, resolution source, shard count)
  hier           gradient-mean lowering chosen for the step program
                 (mode flat|hier, resolution source)
  fusion_threshold
                 gradient-fusion bucket threshold chosen for the step
                 program (threshold bytes or null for per-leaf,
                 resolution source env|tune_db|default)
  pspec          declarative parallelism spec the run's mesh was built
                 from (canonical spec string, resolution source)
  elastic_resize world size changed across a relaunch boundary (n_from,
                 n_to, rescale policy + source, old/new batch and LR)
  kernel_impl    which implementation a Pallas-backed op resolved to in
                 this run (op, impl mosaic|interpret|xla, why) — once
                 per (op, impl), from ops/kernel_impl.py
  run_end        final step, wall s, goodput buckets, MFU, counters,
                 peak HBM per device
  trace_start    a jax.profiler trace window opened (step, artifact path)
  trace_end      the trace window closed (step, artifact path)
  serve_step     one continuous-batching scheduler step (active slots,
                 admissions, tokens produced, queue depth)
  serve_request  a served request retired (prompt/output token counts,
                 TTFT/TPOT ms)
  serve_summary  end-of-loadgen rollup (requests, tokens/sec, devices)
  router_admit   the fleet router accepted a request into its bounded
                 pending queue
  router_shed    admission control rejected a request (429-style: the
                 bounded queue was full; queued = depth at rejection)
  router_dispatch
                 a request was sent to a replica (first placement)
  router_hedge   a straggler request got a second, racing dispatch on
                 another replica (first winner kept)
  router_redispatch
                 an in-flight request was re-dispatched off a draining
                 replica (503 / scrape timeout / dispatch failure)
  router_drain   a replica was marked draining (replica, reason) — no
                 new dispatches; its in-flight work is re-dispatched
  router_request a routed request retired at the router (end-to-end
                 TTFT ms, winning replica, output tokens)
  router_summary end-of-run fleet rollup (completed/shed/hedged/
                 redispatched counts, replicas seen)
  rollout_step   the rollout controller moved one replica through one
                 phase of a rolling weight update (replica, target
                 version, phase ∈ drain/swapped/swap_failed/relaunched/
                 readmitted/promoted/rolled_back)
  rollout_done   a rolling update completed: every replica is on the
                 new version (version, replicas, mixed-version window s)
  rollout_abort  the rollout was rolled back — the canary gate caught a
                 regression (version, the failing metric, reason)
  span_open      a trace span opened (trace id, span id, name; parent
                 span id when not a root) — emitted ONLY through
                 obs.tracing, the sanctioned span API (lint TF123)
  span_close     the span closed (trace, span, same-process monotonic
                 duration ms; outcome fields like status/duplicate/
                 ttft_ms ride along)
  span_note      a trace annotation that is not a timed phase (drain
                 re-queue, rollout swap) — trace id + note text,
                 optionally anchored to a span
  ============== ========================================================

Emission is *best-effort everywhere*: ``emit()`` is a no-op until
``init()`` ran, and never raises after ``close()`` — a broken or absent
event log must not take down a retry loop mid-recovery or a signal
handler mid-preemption.

Enable via ``TPUFRAME_EVENTS_DIR=<dir>`` (train.py also takes
``--events-dir``).  Pure stdlib — no jax import; the writer must work in
the launcher/supervisor before any backend exists, and the offline
analyzer (``python -m tpuframe.obs``) must stay light.
"""

from __future__ import annotations

import json
import os
import re
import socket
import threading
import time

SCHEMA_VERSION = 2

# Every schema this reader still understands.  Bumping SCHEMA_VERSION
# without keeping the predecessor here strands existing logs (and the
# shipped docs/samples/, which the CI selfcheck validates on purpose).
ACCEPTED_SCHEMAS = (1, 2)

ENV_DIR = "TPUFRAME_EVENTS_DIR"
ENV_ATTEMPT = "TPUFRAME_ATTEMPT"

# Per-type required fields (beyond the envelope); the contract the
# ``--selfcheck`` schema validation and the analyzer both enforce.
REQUIRED_FIELDS: dict[str, tuple[str, ...]] = {
    "run_start": ("config", "config_hash", "jax_version"),
    "step": ("step", "wall_ms"),
    "compile": (),
    "ckpt_save": ("step",),
    "ckpt_restore": ("step",),
    "retry": ("op",),
    "fault_injected": ("seam", "kind"),
    "stall": ("last_step", "idle_s"),
    "preempt": ("signal",),
    "devmem": ("devices",),
    "remat_policy": ("policy", "source"),
    "weight_update": ("mode", "source"),
    "hier": ("mode", "source"),
    "fusion_threshold": ("threshold", "source"),
    "pspec": ("spec", "source"),
    "elastic_resize": ("n_from", "n_to", "policy"),
    "kernel_impl": ("op", "impl", "why"),
    "run_end": ("final_step", "wall_s", "goodput"),
    "trace_start": ("step", "path"),
    "trace_end": ("step", "path"),
    "serve_step": ("step", "wall_ms", "active"),
    "serve_request": ("id", "prompt_tokens", "output_tokens", "ttft_ms"),
    "serve_summary": ("requests", "tokens_per_s"),
    "router_admit": ("id",),
    "router_shed": ("id", "queued"),
    "router_dispatch": ("id", "replica"),
    "router_hedge": ("id", "replica"),
    "router_redispatch": ("id", "replica"),
    "router_drain": ("replica", "reason"),
    "router_request": ("id", "replica", "ttft_ms"),
    "router_summary": ("requests", "shed"),
    "rollout_step": ("replica", "version", "phase"),
    "rollout_done": ("version", "replicas"),
    "rollout_abort": ("version", "metric", "reason"),
    # Span events are additive within schema v2 (old readers never see
    # them unless emitted).  obs.tracing.SPAN_REQUIRED_FIELDS pins the
    # same tuples and trace.check() cross-checks the two copies.
    "span_open": ("trace", "span", "name"),
    "span_close": ("trace", "span", "ms"),
    "span_note": ("trace", "note"),
}

_ENVELOPE = ("schema", "type", "t", "host", "proc", "attempt")

_FILE_RE = re.compile(r"^events\.(?P<host>.+)\.jsonl$")

# In-process tee: every record built by any EventLog is also handed to the
# registered listeners (the flight recorder's hook).  Listeners see the
# record BEFORE the file write and regardless of its outcome — a crash
# that tears the JSONL mid-line must not also lose the in-memory copy.
_listeners: list = []


def add_listener(fn) -> None:
    """Register ``fn(record: dict)`` to observe every emitted record.
    Listener exceptions are swallowed (emission never raises)."""
    if fn not in _listeners:
        _listeners.append(fn)


def remove_listener(fn) -> None:
    try:
        _listeners.remove(fn)
    except ValueError:
        pass


def _notify(record: dict) -> None:
    for fn in list(_listeners):
        try:
            fn(record)
        except Exception:  # noqa: BLE001 — a broken listener must not
            pass  # take down the seam that emitted


def _hostname() -> str:
    try:
        return socket.gethostname().split(".")[0] or "host"
    except OSError:
        return "host"


def _process_index() -> int:
    """Rank without forcing a jax import (the fault-registry pattern):
    the launcher env var is authoritative in the fake cluster; jax is
    consulted only when already imported."""
    env = os.environ.get("TPUFRAME_PROCESS_ID")
    if env:
        return int(env)
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:  # noqa: BLE001 — backend not initialized yet
            return 0
    return 0


def attempt_id() -> int:
    """The supervisor-stitched attempt counter (0 on a first launch)."""
    try:
        return int(os.environ.get(ENV_ATTEMPT, "0") or "0")
    except ValueError:
        return 0


class EventLog:
    """Thread-safe JSONL event writer, one file per (host, process).

    The filename doubles as the merge key: ``events.<host>.jsonl`` where
    ``<host>`` is ``<hostname>-p<process index>`` — unique per writer on
    a shared filesystem, reconstructable by the offline merger.  Opened
    in append mode so relaunched attempts extend the same file and the
    analyzer sees one continuous, attempt-tagged stream.
    """

    def __init__(self, directory: str, *, host: str | None = None,
                 proc: int | None = None):
        self.proc = _process_index() if proc is None else proc
        self.host = host or f"{_hostname()}-p{self.proc}"
        os.makedirs(directory, exist_ok=True)
        self.path = os.path.join(directory, f"events.{self.host}.jsonl")
        self._lock = threading.Lock()
        self._fh = open(self.path, "a", buffering=1)
        self._closed = False

    def emit(self, etype: str, **fields) -> dict | None:
        """Append one record; returns it (None when the log is closed).
        Never raises: observability must not take down the run."""
        record = {
            "schema": SCHEMA_VERSION,
            "type": etype,
            "t": round(time.time(), 3),
            "host": self.host,
            "proc": self.proc,
            "attempt": attempt_id(),
            **fields,
        }
        try:
            line = json.dumps(record, default=str)
        except (TypeError, ValueError):
            return None
        _notify(record)
        with self._lock:
            if self._closed:
                return None
            try:
                self._fh.write(line + "\n")
            except (OSError, ValueError):
                return None
        return record

    def close(self) -> None:
        with self._lock:
            if not self._closed:
                self._closed = True
                try:
                    self._fh.close()
                except OSError:
                    pass

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Module-level singleton — the log every instrumented seam writes through.
# ---------------------------------------------------------------------------

_log: EventLog | None = None
_log_lock = threading.Lock()


def init(directory: str | None = None) -> EventLog | None:
    """(Re)open the process-wide event log.  ``directory=None`` consults
    ``TPUFRAME_EVENTS_DIR``; unset/empty means events stay off and every
    ``emit()`` is a cheap no-op."""
    global _log
    directory = directory or os.environ.get(ENV_DIR, "")
    if not directory.strip():
        return None
    with _log_lock:
        if _log is not None:
            _log.close()
        _log = EventLog(directory)
        return _log


def get() -> EventLog | None:
    return _log


def enabled() -> bool:
    return _log is not None


def emit(etype: str, **fields) -> dict | None:
    """Write through the singleton; silent no-op when events are off."""
    log = _log
    if log is None:
        return None
    return log.emit(etype, **fields)


def close() -> None:
    global _log
    with _log_lock:
        if _log is not None:
            _log.close()
            _log = None


# ---------------------------------------------------------------------------
# Reading / validation — the offline half (CLI, tests, CI selfcheck).
# ---------------------------------------------------------------------------

def validate_record(rec: dict) -> list[str]:
    """Problems with one parsed record; empty list means valid."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is not an object: {rec!r:.80}"]
    for key in _ENVELOPE:
        if key not in rec:
            problems.append(f"missing envelope key {key!r}")
    if rec.get("schema") not in ACCEPTED_SCHEMAS:
        problems.append(f"unknown schema version {rec.get('schema')!r} "
                        f"(this reader knows {ACCEPTED_SCHEMAS})")
    etype = rec.get("type")
    if etype in REQUIRED_FIELDS:
        for key in REQUIRED_FIELDS[etype]:
            if key not in rec:
                problems.append(f"{etype} record missing field {key!r}")
    elif etype is not None and etype not in REQUIRED_FIELDS:
        problems.append(f"unknown event type {etype!r}")
    return problems


def read_file(path: str, *, strict: bool = False) -> list[dict]:
    """Parse one events file.  Truncated/garbled trailing lines are
    expected after a crash (the JSONL contract: each durable line is one
    event) and are skipped unless ``strict``."""
    out: list[dict] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                if strict:
                    raise ValueError(f"{path}:{lineno}: unparseable event "
                                     f"line {line!r:.80}")
    return out


def event_files(directory: str) -> list[str]:
    """The ``events.<host>.jsonl`` files under ``directory``, sorted."""
    try:
        names = sorted(os.listdir(directory))
    except (FileNotFoundError, NotADirectoryError):
        if _FILE_RE.match(os.path.basename(directory)):
            return [directory]  # a single file passed directly
        return []
    return [os.path.join(directory, n) for n in names if _FILE_RE.match(n)]


def merge(directory: str) -> list[dict]:
    """All hosts' events, merged into one stream ordered by timestamp
    (ties broken by host then original order — a stable multi-host join,
    the structured replacement for eyeballing N interleaved stdouts)."""
    streams: list[dict] = []
    for path in event_files(directory):
        streams.extend(read_file(path))
    return sorted(streams,
                  key=lambda r: (r.get("t", 0.0), str(r.get("host", ""))))


def validate_files(paths) -> list[str]:
    """Schema-validate whole files (the ``--selfcheck`` surface).
    Strict parsing: a *shipped* sample with a garbled line is a bug even
    though a crashed run's tail is not."""
    problems: list[str] = []
    for path in paths:
        try:
            records = read_file(path, strict=True)
        except (OSError, ValueError) as e:
            problems.append(f"{path}: {e}")
            continue
        if not records:
            problems.append(f"{path}: no events")
        for i, rec in enumerate(records, 1):
            problems += [f"{os.path.basename(path)}:{i}: {p}"
                         for p in validate_record(rec)]
    return problems
