"""The live telemetry plane (PR 9): the OpenMetrics exporter
(``obs/exporter.py``), the crash flight recorder (``obs/flight.py``),
the profiler trace window (``TPUFRAME_TRACE_STEPS``), the ``obs
compare`` regression sentry, and the TF112/TF113 lint rules — plus the
satellite hardening (metrics thread-safety hammer, tensorboard
incremental flush, StepTimeline contract)."""

import json
import os
import pathlib
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import tpuframe
from tpuframe.obs import events
from tpuframe.obs import exporter
from tpuframe.obs import flight
from tpuframe.obs import goodput
from tpuframe.obs import metrics as obs_metrics
from tpuframe.obs.timeline import StepTimeline, parse_trace_steps

_REPO = pathlib.Path(tpuframe.__file__).parent.parent
_SAMPLES = _REPO / "docs" / "samples"

_TRAIN_CMD = [sys.executable, "-m", "tpuframe.train", "--config", "smoke",
              "--set", "total_steps=6", "--set", "log_every=3",
              "--set", "eval_every=6", "--set", "eval_batches=1",
              "--set", "global_batch=16"]


def _train_env(**extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": env.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4",
    })
    env.update(extra)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout=2.0):
    """(status, body) — urllib raises on non-2xx, the exporter's 503 is
    an expected state here."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# Exporter unit surface
# ---------------------------------------------------------------------------

def test_exporter_render_openmetrics_contract():
    obs_metrics.reset_counters()
    obs_metrics.bump("retry.gcs_read.retries", 3)
    try:
        ex = exporter.MetricsExporter()
        ex.set_gauge("tpuframe_step", 7)
        ex.set_gauge("tpuframe_goodput_bucket_seconds", 1.5,
                     bucket="productive")
        ex.add_collector(lambda: [("tpuframe_live", {"k": "v"}, 2.0)])
        text = ex.render()
    finally:
        obs_metrics.reset_counters()
    lines = text.splitlines()
    # Counters: the _total suffix with the TYPE line naming the family
    # WITHOUT it (the OpenMetrics counter contract).
    assert "# TYPE tpuframe_events counter" in lines
    assert ('tpuframe_events_total{name="retry.gcs_read.retries"} 3'
            in lines)
    assert "# TYPE tpuframe_step gauge" in lines
    assert "tpuframe_step 7" in lines
    assert ('tpuframe_goodput_bucket_seconds{bucket="productive"} 1.5'
            in lines)
    assert 'tpuframe_live{k="v"} 2' in lines
    # Exposition terminator: last line is # EOF, trailing newline.
    assert lines[-1] == "# EOF" and text.endswith("\n")


def test_exporter_broken_collector_and_label_escaping():
    ex = exporter.MetricsExporter()

    def broken():
        raise RuntimeError("boom")

    ex.add_collector(broken)
    ex.set_gauge("g", 1.0, path='a"b\nc\\d')
    text = ex.render()
    # The broken collector is skipped, not fatal; labels escape per spec.
    assert 'g{path="a\\"b\\nc\\\\d"} 1' in text


def test_exporter_http_endpoints_and_health_flip():
    state = {"ok": True}
    ex = exporter.MetricsExporter(port=0, health=lambda: state["ok"])
    ex.start()
    assert ex.port and ex.port > 0
    try:
        base = f"http://127.0.0.1:{ex.port}"
        status, body = _get(f"{base}/metrics")
        assert status == 200 and body.rstrip().endswith("# EOF")
        status, body = _get(f"{base}/healthz")
        assert status == 200 and body == "ok\n"
        state["ok"] = False
        status, body = _get(f"{base}/healthz")
        assert status == 503 and body == "unhealthy\n"
        status, _ = _get(f"{base}/nope")
        assert status == 404
    finally:
        ex.stop()


def test_exporter_broken_health_probe_reads_unhealthy():
    def probe():
        raise RuntimeError("probe died")

    assert exporter.MetricsExporter(health=probe).healthy() is False


def test_exporter_textfile_flush(tmp_path):
    path = str(tmp_path / "sub" / "metrics.prom")
    ex = exporter.MetricsExporter(textfile=path)
    ex.set_gauge("tpuframe_step", 3)
    ex.flush()
    first = open(path).read()
    assert "tpuframe_step 3" in first and first.rstrip().endswith("# EOF")
    ex.set_gauge("tpuframe_step", 4)
    ex.stop()  # stop() re-flushes
    assert "tpuframe_step 4" in open(path).read()
    # Atomic rewrite: no tmp litter left behind.
    assert os.listdir(tmp_path / "sub") == ["metrics.prom"]


def test_start_from_env_gating(monkeypatch, tmp_path):
    monkeypatch.delenv(exporter.ENV_PORT, raising=False)
    monkeypatch.delenv(exporter.ENV_TEXTFILE, raising=False)
    exporter.stop()
    assert exporter.start_from_env() is None  # off unless asked
    monkeypatch.setenv(exporter.ENV_TEXTFILE, str(tmp_path / "m.prom"))
    ex = exporter.start_from_env()
    try:
        assert ex is not None and ex.port is None  # textfile-only mode
        assert exporter.start_from_env() is ex     # idempotent singleton
    finally:
        exporter.stop()
    assert exporter.get() is None


def test_exporter_stop_is_idempotent():
    ex = exporter.MetricsExporter(port=0).start()
    assert ex.port and ex.port > 0
    ex.stop()
    ex.stop()  # second stop must be a no-op, not a crash
    # and the module-level stop() with no exporter alive is too
    exporter.stop()
    exporter.stop()


def test_exporter_occupied_port_falls_back_to_ephemeral(capsys):
    """A fleet launching N replicas on one host with the same port knob
    must not lose N-1 scrape planes: the loser of the bind race serves
    from an ephemeral port (on ``.port``) instead of crashing or going
    silently scrape-less."""
    first = exporter.MetricsExporter(port=0).start()
    try:
        second = exporter.MetricsExporter(port=first.port).start()
        try:
            assert second.port and second.port != first.port
            status, body = _get(f"http://127.0.0.1:{second.port}/metrics")
            assert status == 200 and body.rstrip().endswith("# EOF")
        finally:
            second.stop()
        assert "fell back to ephemeral port" in capsys.readouterr().err
    finally:
        first.stop()


def test_exporter_post_handler_round_trip():
    ex = exporter.MetricsExporter(port=0).start()
    try:
        ex.add_handler("/echo", lambda body: (200, body.upper()))

        def boom(body):
            raise RuntimeError("handler boom")

        ex.add_handler("/boom", boom)
        base = f"http://127.0.0.1:{ex.port}"

        def post(path, data):
            req = urllib.request.Request(f"{base}{path}", data=data)
            try:
                with urllib.request.urlopen(req, timeout=2.0) as r:
                    return r.status, r.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        status, body = post("/echo", b"fleet")
        assert (status, body) == (200, b"FLEET")
        status, _ = post("/nowhere", b"x")
        assert status == 404
        status, body = post("/boom", b"x")  # 500, server stays up
        assert status == 500 and b"RuntimeError" in body
        status, body = post("/echo", b"still alive")
        assert (status, body) == (200, b"STILL ALIVE")
    finally:
        ex.stop()


def test_router_scrape_503_redispatches_with_zero_loss():
    """End-to-end over real HTTP: replica A accepts a request then its
    /healthz flips 503 mid-generation; the router's scrape marks it
    draining (``router_drain``), re-dispatches the in-flight request to
    replica B (``router_redispatch``), and the admitted request retires
    exactly once — zero loss, first winner kept."""
    from tpuframe.serve.router import Router

    a_state = {"ok": True}
    a_release = threading.Event()

    def a_generate(body):
        msg = json.loads(body.decode())
        a_state["ok"] = False          # health flips mid-generation
        a_release.wait(10.0)           # ...and A stalls on the answer
        return 200, json.dumps({"rid": msg["rid"], "tokens": [1],
                                "ttft_ms": 1.0}).encode()

    def b_generate(body):
        msg = json.loads(body.decode())
        return 200, json.dumps({"rid": msg["rid"], "tokens": [1, 2],
                                "ttft_ms": 2.0}).encode()

    ex_a = exporter.MetricsExporter(port=0,
                                    health=lambda: a_state["ok"]).start()
    ex_b = exporter.MetricsExporter(port=0).start()
    try:
        ex_a.add_handler("/generate", a_generate)
        ex_b.add_handler("/generate", b_generate)
        router = Router(
            [f"http://127.0.0.1:{ex_a.port}",
             f"http://127.0.0.1:{ex_b.port}"],
            queue_limit=8, hedge_ms=0,  # no hedging: drain does the work
            scrape_interval_s=0.01, scrape_timeout_s=1.0,
            dispatch_timeout_s=15.0)
        assert router.submit(7, [1, 2, 3], 4)
        deadline = time.monotonic() + 15.0
        while router.has_work() and time.monotonic() < deadline:
            router.step()
            time.sleep(0.005)
        summary = router.summary()
    finally:
        a_release.set()
        ex_a.stop()
        ex_b.stop()
    assert summary["admitted"] == 1 and summary["requests"] == 1
    assert summary["lost"] == 0
    assert summary["drains"] == 1 and summary["redispatched"] == 1
    (req,) = router.completed
    assert req.replica == "r1" and req.result["tokens"] == [1, 2]


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_is_bounded_and_dump_payload(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUFRAME_ATTEMPT", "2")
    rec = flight.FlightRecorder(str(tmp_path), maxlen=4)
    for i in range(10):
        rec.record({"type": "step", "step": i})
    assert [r["step"] for r in rec.snapshot()] == [6, 7, 8, 9]
    path = rec.dump("unit_test")
    assert path and os.path.basename(path) == "flight_2.json"
    payload = json.load(open(path))
    assert payload["reason"] == "unit_test"
    assert payload["attempt"] == 2
    assert [r["step"] for r in payload["events"]] == [6, 7, 8, 9]
    assert isinstance(payload["counters"], dict)


def test_flight_listener_tees_even_when_write_fails(tmp_path):
    """The ring must hold the record even when the JSONL write is torn —
    that's the whole point of dumping from memory, not from the file."""
    log = events.init(str(tmp_path))
    rec = flight.install(str(tmp_path), maxlen=8)
    try:
        log.emit("step", step=1, wall_ms=10.0)
        log._fh.close()  # simulate a torn/closed file descriptor
        log.emit("step", step=2, wall_ms=11.0)  # write fails, no raise
        steps = [r["step"] for r in rec.snapshot() if r["type"] == "step"]
        assert steps == [1, 2]
    finally:
        flight.uninstall()
        events.close()


def test_flight_dump_noop_when_uninstalled():
    flight.uninstall()
    assert flight.get() is None
    assert flight.dump("nothing") is None


def test_flight_install_env_gating(tmp_path, monkeypatch):
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    assert flight.install() is None  # no directory anywhere: off
    monkeypatch.setenv(events.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(flight.ENV_EVENTS, "3")
    rec = flight.install()
    try:
        assert rec is not None and rec._ring.maxlen == 3
    finally:
        flight.uninstall()


# ---------------------------------------------------------------------------
# Satellites: counter thread-safety, tensorboard incremental flush,
# StepTimeline contract, parse_trace_steps
# ---------------------------------------------------------------------------

def test_metrics_bump_hammer_threads_exact_total():
    obs_metrics.reset_counters()
    n_threads, n_bumps = 8, 2000

    def hammer():
        for _ in range(n_bumps):
            obs_metrics.bump("hammer.total")
            obs_metrics.bump("hammer.weighted", 2)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = obs_metrics.counters()
    obs_metrics.reset_counters()
    assert got["hammer.total"] == n_threads * n_bumps
    assert got["hammer.weighted"] == 2 * n_threads * n_bumps


def test_tensorboard_local_flush_is_incremental(tmp_path):
    from tpuframe.obs.tensorboard import SummaryWriter

    w = SummaryWriter(str(tmp_path), flush_every=1000)
    w.add_scalar("loss", 2.0, 1)
    w.flush()
    size1 = os.path.getsize(w.path)
    # The in-memory buffer drains on local flush — flushed history lives
    # on disk, not in RAM (the O(n^2) rewrite this satellite removed).
    assert len(w._buf) == 0
    w.add_scalar("loss", 1.0, 2)
    w.flush()
    size2 = os.path.getsize(w.path)
    assert size2 > size1
    w.close()
    # Appended increments must still parse as one well-formed stream.
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader)

    loaded = list(EventFileLoader(w.path).Load())
    tags = [v.tag for e in loaded for v in e.summary.value]
    assert tags.count("loss") == 2


def test_step_timeline_chrome_trace_fields(tmp_path):
    tl = StepTimeline(str(tmp_path / "t.json"))
    with tl.phase("data_wait", step=3):
        pass
    with tl.phase("train_step", step=3):
        pass
    tl.instant("preempted", step=3)
    tl.close()
    doc = json.load(open(tl.path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["data_wait", "train_step",
                                       "preempted"]
    for e in evs:
        assert {"ph", "ts", "pid", "tid"} <= set(e)
    assert evs[0]["ph"] == "X" and evs[0]["dur"] >= 0
    assert evs[2]["ph"] == "i"


def test_step_timeline_multihost_proc_suffix(tmp_path, monkeypatch):
    import tpuframe.obs.timeline as timeline_mod

    monkeypatch.setattr(timeline_mod.jax, "process_count", lambda: 2)
    monkeypatch.setattr(timeline_mod.jax, "process_index", lambda: 1)
    tl = StepTimeline(str(tmp_path / "t.json"))
    assert tl.path.endswith("t.proc1.json")
    tl.instant("x")
    tl.close()
    assert json.load(open(tl.path))["traceEvents"][0]["pid"] == 1


def test_parse_trace_steps():
    assert parse_trace_steps("100:5") == (100, 5)
    assert parse_trace_steps(" 0:1 ") == (0, 1)
    for bad in (None, "", "  ", "5", "a:b", "1:2:3", "-1:5", "3:0",
                "3:-2", "1.5:2"):
        assert parse_trace_steps(bad) is None, bad


# ---------------------------------------------------------------------------
# events listener seam + new schema types
# ---------------------------------------------------------------------------

def test_events_listener_tee_and_removal(tmp_path):
    seen = []
    events.add_listener(seen.append)
    try:
        log = events.EventLog(str(tmp_path))
        log.emit("trace_start", step=5, path="/tmp/trace")
        log.emit("trace_end", step=8, path="/tmp/trace")
        log.close()
    finally:
        events.remove_listener(seen.append)
    assert [r["type"] for r in seen] == ["trace_start", "trace_end"]
    # The new types are registered schema types, not validation leaks.
    for r in seen:
        assert events.validate_record(r) == []
    # After removal the tee is dead.
    log2 = events.EventLog(str(tmp_path))
    log2.emit("step", step=1, wall_ms=1.0)
    log2.close()
    assert len(seen) == 2


def test_events_broken_listener_does_not_break_emit(tmp_path):
    def broken(rec):
        raise RuntimeError("listener bug")

    events.add_listener(broken)
    try:
        log = events.EventLog(str(tmp_path))
        assert log.emit("step", step=1, wall_ms=1.0) is not None
        log.close()
    finally:
        events.remove_listener(broken)


# ---------------------------------------------------------------------------
# compare — the regression sentry
# ---------------------------------------------------------------------------

def test_compare_runs_flags_golden_pair():
    a = events.merge(str(_SAMPLES / "compare_fast"))
    b = events.merge(str(_SAMPLES / "compare_slow"))
    result = goodput.compare_runs(a, b)
    flagged = {r["metric"] for r in result["regressions"]}
    assert {"step_p50_ms", "mfu_productive",
            "serve_ttft_p90_ms"} <= flagged
    # Identity is clean in BOTH directions of the threshold.
    assert goodput.compare_runs(a, a)["regressions"] == []
    # The fast run against the slow baseline is an improvement, not a
    # regression.
    back = goodput.compare_runs(b, a)
    assert back["regressions"] == [] and back["improvements"]


def test_compare_runs_skips_one_sided_metrics():
    """A metric only participates when both runs carry it — a baseline
    without serving traffic must not 'regress' on TTFT."""
    a = events.merge(str(_SAMPLES / "compare_fast"))
    training_only = [r for r in a if not r["type"].startswith("serve")]
    result = goodput.compare_runs(training_only, a)
    assert "serve_ttft_p90_ms" not in result["metrics"]


def test_compare_thresholds_overridable():
    a = events.merge(str(_SAMPLES / "compare_fast"))
    b = events.merge(str(_SAMPLES / "compare_slow"))
    # Thresholds wide enough that nothing regresses.
    loose = goodput.compare_runs(a, b, thresholds={
        "step_pct": 1e6, "productive_drop": 1.0, "mfu_drop": 1.0,
        "serve_pct": 1e6})
    assert loose["regressions"] == []


def test_obs_cli_compare_exit_codes(capsys):
    from tpuframe.obs.__main__ import main

    fast, slow = str(_SAMPLES / "compare_fast"), str(_SAMPLES
                                                     / "compare_slow")
    assert main(["compare", fast, slow]) == 1
    out = capsys.readouterr().out
    assert "COMPARE-REGRESSION [step_p50_ms]" in out
    assert main(["compare", fast, fast]) == 0
    # Threshold flags reach the checks.
    assert main(["compare", fast, slow, "--step-pct", "1e6",
                 "--mfu-drop", "1", "--serve-pct", "1e6",
                 "--prod-drop", "1"]) == 0


def test_obs_selfcheck_includes_compare_golden(capsys):
    from tpuframe.obs.__main__ import main

    assert main(["summarize", "--selfcheck"]) == 0
    out = capsys.readouterr().out
    assert "0 problem(s)" in out


def test_selfcheck_catches_blind_sentry(tmp_path, monkeypatch):
    """If the golden pair ever stops flagging (threshold drift), the
    selfcheck must fail CI — prove it by pointing the sample root at a
    copy where fast == slow."""
    import tpuframe.obs.__main__ as obs_main

    root = tmp_path / "samples"
    for name in ("compare_fast", "compare_slow"):
        d = root / name
        d.mkdir(parents=True)
        src = _SAMPLES / "compare_fast" / "events.compare-0-p0.jsonl"
        (d / "events.compare-0-p0.jsonl").write_text(src.read_text())
    monkeypatch.setattr(obs_main, "_samples_root", lambda: str(root))
    problems = obs_main._selfcheck_compare()
    assert problems and "blind" in problems[0]


# ---------------------------------------------------------------------------
# TF112 / TF113 lint rules
# ---------------------------------------------------------------------------

def test_tf112_unregistered_event_type():
    from tpuframe.analysis.source_lint import lint_source

    src = ("from tpuframe.obs import events as events_lib\n"
           "def f():\n"
           "    events_lib.emit('not_a_type', x=1)\n"
           "    events_lib.emit('step', step=1, wall_ms=2.0)\n"
           "    obs_events.emit('also_bogus')\n"
           "    events_lib.emit(computed_name, x=1)\n")
    findings = [f for f in lint_source(src, "tpuframe/x.py")
                if f.rule == "TF112"]
    assert len(findings) == 2  # both literals flagged, computed skipped
    assert "not_a_type" in findings[0].message


def test_tf112_registry_matches_import():
    """The AST-extracted registry and the real REQUIRED_FIELDS can never
    drift — same source of truth, two readers."""
    from tpuframe.analysis.source_lint import _event_type_registry

    assert _event_type_registry() == frozenset(events.REQUIRED_FIELDS)


def test_tf113_http_server_fenced():
    from tpuframe.analysis.source_lint import lint_source

    src = "import http.server\nfrom http.server import HTTPServer\n"
    assert len([f for f in lint_source(src, "tpuframe/serve/api.py")
                if f.rule == "TF113"]) == 2
    # The exporter is the sanctioned endpoint.
    assert [f for f in lint_source(src, "tpuframe/obs/exporter.py")
            if f.rule == "TF113"] == []


def test_lint_gate_clean_on_tree():
    """The repo's own tree must pass the new rules (the analysis CI
    gate runs them over tpuframe/)."""
    from tpuframe.analysis.source_lint import lint_paths

    findings = [f for f in lint_paths([str(_REPO / "tpuframe")])
                if f.rule in ("TF112", "TF113")]
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# End-to-end through the harness (CPU mesh, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_exporter_scrape_through_harness(tmp_path):
    """A live scrape during training serves goodput buckets, and the
    final exposition's bucket-seconds sum matches the offline summarize
    recompute (same books, two readers)."""
    evdir = str(tmp_path / "events")
    textfile = str(tmp_path / "metrics.prom")
    port = _free_port()
    proc = subprocess.Popen(
        _TRAIN_CMD, env=_train_env(
            TPUFRAME_EVENTS_DIR=evdir,
            TPUFRAME_METRICS_PORT=str(port),
            TPUFRAME_METRICS_TEXTFILE=textfile),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    live_scrapes = []
    try:
        deadline = time.time() + 500
        while proc.poll() is None and time.time() < deadline:
            try:
                status, body = _get(
                    f"http://127.0.0.1:{port}/metrics", timeout=1.0)
                if status == 200:
                    live_scrapes.append(body)
                hstatus, hbody = _get(
                    f"http://127.0.0.1:{port}/healthz", timeout=1.0)
                if hstatus == 200:
                    assert hbody == "ok\n"  # healthy while stepping
            except Exception:  # noqa: BLE001 — not up yet / mid-shutdown
                pass
            time.sleep(0.3)
        rc = proc.wait(timeout=60)
    finally:
        proc.kill()
        out, err = proc.communicate()
    assert rc == 0, err[-1500:]
    assert live_scrapes, "no successful live scrape during the run"
    assert any("tpuframe_goodput_bucket_seconds" in s
               for s in live_scrapes)

    # Final exposition (stop()'s flush) vs the offline recompute.
    final = open(textfile).read()
    bucket_sum = sum(
        float(line.rsplit(" ", 1)[1]) for line in final.splitlines()
        if line.startswith("tpuframe_goodput_bucket_seconds{"))
    summary = goodput.from_events(events.merge(evdir))
    assert bucket_sum == pytest.approx(sum(summary["buckets"].values()),
                                       rel=0.02, abs=0.25)
    assert bucket_sum == pytest.approx(summary["wall_s"],
                                       rel=0.02, abs=0.25)


@pytest.mark.slow
def test_healthz_flips_on_injected_stall(tmp_path):
    """An injected hang flips /healthz to 503 (the heartbeat watchdog is
    the health probe).  Stall-abort is disabled so the unhealthy window
    is observable instead of ~ms wide."""
    port = _free_port()
    proc = subprocess.Popen(
        _TRAIN_CMD, env=_train_env(
            TPUFRAME_EVENTS_DIR=str(tmp_path / "events"),
            TPUFRAME_METRICS_PORT=str(port),
            TPUFRAME_STALL_TIMEOUT_S="3", TPUFRAME_STALL_POLL_S="0.5",
            TPUFRAME_STALL_ABORT="0", TPUFRAME_HANG_STEP="3"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        flipped = False
        deadline = time.time() + 500
        while time.time() < deadline and proc.poll() is None:
            try:
                status, _ = _get(f"http://127.0.0.1:{port}/healthz",
                                 timeout=1.0)
                if status == 503:
                    flipped = True
                    break
            except Exception:  # noqa: BLE001 — not up yet / mid-shutdown
                pass
            time.sleep(0.3)
        assert flipped, "healthz never flipped to 503 during the hang"
    finally:
        proc.kill()
        proc.communicate()


@pytest.mark.slow
def test_crash_fault_leaves_flight_dump(tmp_path):
    """A kind=crash fault (os._exit(42), no handler can run) still
    leaves a flight dump whose tail matches the JSONL log."""
    evdir = str(tmp_path / "events")
    out = subprocess.run(
        _TRAIN_CMD, env=_train_env(
            TPUFRAME_EVENTS_DIR=evdir,
            TPUFRAME_FAULTS="host:step=3:kind=crash"),
        capture_output=True, text=True, timeout=500)
    assert out.returncode == 42, out.stderr[-1500:]
    dump_path = os.path.join(evdir, "flight_0.json")
    assert os.path.exists(dump_path), os.listdir(evdir)
    payload = json.load(open(dump_path))
    assert payload["reason"] == "crash_injected"
    ring = payload["events"]
    assert ring and ring[-1]["type"] == "fault_injected"
    # The ring's tail IS the log's tail (same records, memory copy).
    # Compare (type, t) pairs: values that json.dumps(default=str)
    # stringified round-trip differently, the identity keys don't.
    logged = events.read_file(events.event_files(evdir)[0])
    ring_tail = [(r["type"], r["t"]) for r in ring]
    log_tail = [(r["type"], r["t"]) for r in logged]
    n = min(len(ring_tail), len(log_tail))
    assert n >= 3
    assert ring_tail[-n:] == log_tail[-n:]


@pytest.mark.slow
def test_trace_steps_window_through_harness(tmp_path):
    """TPUFRAME_TRACE_STEPS captures a profiler window and announces it
    as typed trace_start/trace_end events carrying the artifact path."""
    evdir = str(tmp_path / "events")
    out = subprocess.run(
        _TRAIN_CMD, env=_train_env(
            TPUFRAME_EVENTS_DIR=evdir, TPUFRAME_TRACE_STEPS="3:2"),
        capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-1500:]
    merged = events.merge(evdir)
    starts = [r for r in merged if r["type"] == "trace_start"]
    ends = [r for r in merged if r["type"] == "trace_end"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["step"] == 3 and ends[0]["step"] == 5
    trace_path = starts[0]["path"]
    assert trace_path == ends[0]["path"]
    assert os.path.isdir(trace_path)  # the artifact actually landed
    assert events.validate_files(events.event_files(evdir)) == []
