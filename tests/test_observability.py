"""Observability: step timeline (HOROVOD_TIMELINE parity) + fusion-threshold
knob (HOROVOD_FUSION_THRESHOLD parity) — SURVEY.md §5.1, §3b — plus the
obs v2 surface: structured run events, goodput/MFU accounting, devmem
telemetry, and the ``python -m tpuframe.obs`` analyzer."""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

import tpuframe
from tpuframe.obs import devmem
from tpuframe.obs import events
from tpuframe.obs import goodput
from tpuframe.obs import metrics as obs_metrics
from tpuframe.obs.heartbeat import Heartbeat
from tpuframe.obs.timeline import StepTimeline
from tpuframe.parallel import tuning

_REPO = pathlib.Path(tpuframe.__file__).parent.parent
_SAMPLES = str(_REPO / "docs" / "samples")


def test_step_timeline_events(tmp_path):
    path = str(tmp_path / "trace.json")
    tl = StepTimeline(path)
    with tl.phase("train_step", step=1):
        pass
    tl.instant("fault", reason="test")
    tl.close()
    trace = json.load(open(path))
    names = [e["name"] for e in trace["traceEvents"]]
    assert names == ["train_step", "fault"]
    ev = trace["traceEvents"][0]
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["args"] == {"step": 1}


def test_from_env_disabled_by_default(monkeypatch):
    monkeypatch.delenv("TPUFRAME_TIMELINE", raising=False)
    assert StepTimeline.from_env() is None


def test_tensorboard_events_stock_readable(tmp_path):
    """Our hand-encoded event files must parse with tensorboard's OWN loader
    (SURVEY.md §5.5 'event files a stock TensorBoard can read')."""
    from tpuframe.obs.tensorboard import SummaryWriter

    w = SummaryWriter(str(tmp_path))
    w.add_scalars(1, {"loss": 2.5, "skip_me": "str"}, prefix="train")
    w.add_scalars(2, {"loss": 1.25}, prefix="train")
    w.add_scalar("eval/acc", 0.75, 2)
    w.close()

    files = [f for f in os.listdir(tmp_path) if "tfevents" in f]
    assert len(files) == 1
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader)

    events = list(EventFileLoader(str(tmp_path / files[0])).Load())
    assert events[0].file_version == "brain.Event:2"
    # TB's loader migrates simple_value -> rank-0 tensor (data_compat);
    # handle both, as a stock TB frontend does.
    scalars = [(v.tag, e.step,
                v.simple_value if v.WhichOneof("value") == "simple_value"
                else v.tensor.float_val[0])
               for e in events for v in e.summary.value]
    assert ("train/loss", 1, 2.5) in scalars
    assert ("train/loss", 2, 1.25) in scalars
    assert ("eval/acc", 2, 0.75) in scalars
    assert not any(t == "train/skip_me" for t, _, _ in scalars)


def test_metric_logger_tb_sink(tmp_path):
    from tpuframe.obs.metrics import MetricLogger

    logger = MetricLogger(None, stdout=False, tb_dir=str(tmp_path / "tb"))
    logger.log(3, {"loss": 0.5, "accuracy": 0.9})
    logger.log(3, {"accuracy": 0.8}, prefix="eval")
    logger.close()
    files = [f for f in os.listdir(tmp_path / "tb") if "tfevents" in f]
    assert len(files) == 1
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader)

    tags = {v.tag for e in EventFileLoader(
        str(tmp_path / "tb" / files[0])).Load() for v in e.summary.value}
    assert {"train/loss", "train/accuracy", "eval/accuracy"} <= tags


def test_fusion_flags_shape():
    flags = tuning.fusion_flags(64 * 1024 * 1024)
    assert any("all_reduce_combine_threshold_bytes=67108864" in f
               for f in flags)


def test_apply_after_backend_init_refuses():
    # jax backend is live in the test process — apply must refuse, not lie.
    assert tuning.apply(1 << 20) is False


@pytest.mark.slow
def test_fusion_env_applies_in_fresh_process():
    code = (
        "import os; os.environ['TPUFRAME_FUSION_THRESHOLD'] = str(1 << 25)\n"
        "from tpuframe.parallel import bootstrap, tuning\n"
        "bootstrap.initialize()\n"
        "assert tuning.current() == 1 << 25, tuning.current()\n"
        "assert 'combine_threshold_bytes=33554432' in os.environ['XLA_FLAGS']\n"
        "import jax; jax.numpy.zeros(2).block_until_ready()\n"
        "print('FUSION_OK')\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "FUSION_OK" in out.stdout, out.stderr[-800:]


@pytest.mark.slow
def test_timeline_through_harness(tmp_path):
    path = str(tmp_path / "tl.json")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": env.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4",
        "TPUFRAME_TIMELINE": path,
    })
    out = subprocess.run(
        [sys.executable, "-m", "tpuframe.train", "--config", "smoke",
         "--set", "total_steps=6", "--set", "log_every=3",
         "--set", "eval_every=6", "--set", "eval_batches=1",
         "--set", "global_batch=16"],
        env=env, capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-1500:]
    trace = json.load(open(path))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"train.data_wait", "train.step", "train.eval"} <= names
    # the loader's worker is on the same timeline, on a thread of its own
    assert {"loader.gather", "loader.put", "loader.wait"} <= names
    steps = [e for e in trace["traceEvents"] if e["name"] == "train.step"]
    assert len(steps) == 6
    gathers = [e for e in trace["traceEvents"] if e["name"] == "loader.gather"]
    assert {e["tid"] for e in gathers}.isdisjoint({e["tid"] for e in steps})


# ---------------------------------------------------------------------------
# obs v2: structured run events.
# ---------------------------------------------------------------------------

def _rec(t, etype, host="h0-p0", attempt=0, **kw):
    return {"schema": 1, "type": etype, "t": t, "host": host, "proc": 0,
            "attempt": attempt, **kw}


def _write_events(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_event_log_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv(events.ENV_ATTEMPT, "3")
    log = events.EventLog(str(tmp_path), host="h0-p0", proc=0)
    log.emit("step", step=7, wall_ms=12.5)
    log.emit("ckpt_save", step=7, ms=30.0, async_write=False)
    log.close()
    # Emission after close is a silent no-op, never a raise.
    assert log.emit("step", step=8, wall_ms=1.0) is None
    back = events.read_file(log.path, strict=True)
    assert [r["type"] for r in back] == ["step", "ckpt_save"]
    assert back[0]["step"] == 7 and back[0]["attempt"] == 3
    assert back[0]["schema"] == events.SCHEMA_VERSION
    assert all(events.validate_record(r) == [] for r in back)
    assert events.validate_files([log.path]) == []


def test_event_singleton_off_by_default(monkeypatch):
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    events.close()
    assert events.init() is None
    assert not events.enabled()
    assert events.emit("step", step=1, wall_ms=1.0) is None


def test_event_log_append_across_attempts(tmp_path):
    # Relaunched attempts reopen the same per-host file in append mode —
    # one continuous, attempt-tagged stream.
    a = events.EventLog(str(tmp_path), host="h0-p0", proc=0)
    a.emit("step", step=1, wall_ms=5.0)
    a.close()
    b = events.EventLog(str(tmp_path), host="h0-p0", proc=0)
    b.emit("step", step=2, wall_ms=5.0)
    b.close()
    assert a.path == b.path
    assert [r["step"] for r in events.read_file(a.path)] == [1, 2]


def test_event_read_skips_torn_tail(tmp_path):
    p = tmp_path / "events.h0-p0.jsonl"
    _write_events(p, [_rec(1.0, "step", step=1, wall_ms=5.0)])
    with open(p, "a") as f:
        f.write('{"schema": 1, "type": "step", "t": 2.0, "ho')  # crash tear
    assert [r["step"] for r in events.read_file(str(p))] == [1]
    with pytest.raises(ValueError, match="unparseable"):
        events.read_file(str(p), strict=True)
    assert events.validate_files([str(p)])  # selfcheck is strict


def test_event_merge_orders_across_hosts(tmp_path):
    _write_events(tmp_path / "events.b-p1.jsonl",
                  [_rec(2.0, "step", host="b-p1", step=2, wall_ms=1.0),
                   _rec(4.0, "step", host="b-p1", step=3, wall_ms=1.0)])
    _write_events(tmp_path / "events.a-p0.jsonl",
                  [_rec(1.0, "step", host="a-p0", step=1, wall_ms=1.0),
                   _rec(2.0, "step", host="a-p0", step=2, wall_ms=1.0)])
    (tmp_path / "not-events.txt").write_text("ignored")
    merged = events.merge(str(tmp_path))
    assert [(r["t"], r["host"]) for r in merged] == [
        (1.0, "a-p0"), (2.0, "a-p0"), (2.0, "b-p1"), (4.0, "b-p1")]


def test_validate_record_catches_contract_breaks():
    good = _rec(1.0, "stall", last_step=4, idle_s=9.0)
    assert events.validate_record(good) == []
    # Both shipped schema generations read; an unknown future one fails.
    assert events.validate_record({**good, "schema": 2}) == []
    assert events.SCHEMA_VERSION in events.ACCEPTED_SCHEMAS
    assert events.validate_record({**good, "schema": 99})
    assert events.validate_record(_rec(1.0, "no_such_type"))
    missing = _rec(1.0, "run_end")  # no final_step/wall_s/goodput
    assert len(events.validate_record(missing)) == 3


# ---------------------------------------------------------------------------
# obs v2: goodput / MFU accounting.
# ---------------------------------------------------------------------------

def test_goodput_meter_buckets_sum_to_wall():
    now = [100.0]
    m = goodput.GoodputMeter(clock=lambda: now[0])
    m.step(10.0)              # first step = compile
    m.step(1.0)
    m.step(1.0)
    m.charge("ckpt", 2.0)
    m.charge("stall", 3.0)
    now[0] += 20.0
    s = m.summary()
    assert s["steps"] == 3 and s["productive_steps"] == 2
    assert s["buckets"]["compile"] == 10.0
    assert s["buckets"]["productive"] == 2.0
    assert s["buckets"]["other"] == pytest.approx(20.0 - 17.0)
    assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"])
    with pytest.raises(ValueError):
        m.charge("nonsense", 1.0)


def test_mfu_arithmetic_and_guards():
    hw = pytest.importorskip("tpuframe.tune.roofline").get_hardware("v5e")
    # One device running at exactly half the bf16 peak for one second.
    assert goodput.mfu(hw.bf16_flops / 2, 1.0, generation="v5e",
                       n_devices=1) == pytest.approx(0.5)
    # Peak scales with slice size.
    assert goodput.mfu(hw.bf16_flops, 1.0, generation="v5e",
                       n_devices=4) == pytest.approx(0.25)
    assert goodput.mfu(0.0, 1.0, generation="v5e") == 0.0
    assert goodput.mfu(1e12, 0.0, generation="v5e") == 0.0
    assert goodput.flops_fallback(10, 4, 2) == 6.0 * 10 * 4 * 2


def test_from_events_crashed_attempt_reconstruction():
    # No run_end anywhere: buckets rebuilt from raw step/ckpt/stall
    # events, "other" absorbing the unattributed remainder of the span.
    stream = [
        _rec(0.0, "run_start", config="c", config_hash="h",
             jax_version="j", devices=2, flops_per_step=1e12,
             generation="v5e"),
        _rec(10.0, "step", step=1, wall_ms=9000.0),
        _rec(11.0, "step", step=2, wall_ms=500.0),
        _rec(12.0, "step", step=3, wall_ms=500.0),
        _rec(13.0, "ckpt_save", step=3, ms=1000.0),
        _rec(20.0, "stall", last_step=3, idle_s=5.0),
    ]
    s = goodput.from_events(stream)
    assert s["attempts"] == 1 and s["steps"] == 3 and s["final_step"] == 3
    b = s["buckets"]
    assert b["compile"] == 9.0 and b["productive"] == 1.0
    assert b["ckpt"] == 1.0 and b["stall"] == 5.0
    assert s["wall_s"] == 20.0
    assert sum(b.values()) == pytest.approx(s["wall_s"])
    # MFU recomputed offline from the run_start flops model.
    assert s["mfu_productive"] == pytest.approx(
        goodput.mfu(1e12, 0.5, generation="v5e", n_devices=2))


def test_from_events_stitches_restarts_on_samples():
    # The shipped docs/samples log: attempt 0 crashes at step 7, attempt
    # 1 resumes from the step-5 checkpoint and completes.
    merged = events.merge(_SAMPLES)
    assert merged, "docs/samples event files missing"
    s = goodput.from_events(merged)
    assert s["attempts"] == 2
    assert s["restart_lost_s"] > 0 and s["retrained_steps"] == 1
    assert s["final_step"] == 12
    assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"],
                                                       abs=0.01)
    assert s["mfu_productive"] > 0
    assert s["peak_hbm_bytes"] == 6200000000


# ---------------------------------------------------------------------------
# obs v2: anomaly detection.
# ---------------------------------------------------------------------------

def test_anomaly_step_regression_rolling_median():
    steps = [_rec(float(i), "step", step=i, wall_ms=100.0)
             for i in range(1, 10)]
    steps[7]["wall_ms"] = 450.0  # 4.5x the rolling median
    found = goodput.find_anomalies(steps + [
        _rec(99.0, "run_end", final_step=9, wall_s=9.0, goodput={})])
    kinds = [f["kind"] for f in found]
    assert kinds == ["step_regression"]
    assert found[0]["step"] == 8
    # The compile step never trips the detector.
    first_slow = [_rec(0.0, "step", step=1, wall_ms=90000.0)] + steps[1:]
    found2 = goodput.find_anomalies(first_slow + [
        _rec(99.0, "run_end", final_step=9, wall_s=9.0, goodput={})])
    assert [f["kind"] for f in found2] == ["step_regression"]


def test_anomaly_stall_retry_storm_no_run_end():
    stream = ([_rec(float(i), "retry", op="gcs_read", outcome="retrying")
               for i in range(6)]
              + [_rec(30.0, "stall", last_step=4, idle_s=12.0),
                 _rec(31.0, "step", step=4, wall_ms=10.0)])
    found = goodput.find_anomalies(stream)
    kinds = sorted(f["kind"] for f in found)
    assert kinds == ["no_run_end", "retry_storm", "stall"]
    storm = next(f for f in found if f["kind"] == "retry_storm")
    # One report per stream, raised at the first threshold crossing.
    assert storm["count"] == 5


def test_anomaly_low_mfu_opt_in():
    stream = [
        _rec(0.0, "run_start", config="c", config_hash="h",
             jax_version="j", devices=1, flops_per_step=1.0,
             generation="v5e"),
        _rec(1.0, "step", step=1, wall_ms=100.0),
        _rec(2.0, "step", step=2, wall_ms=100.0),
        _rec(3.0, "run_end", final_step=2, wall_s=3.0, goodput={}),
    ]
    assert goodput.find_anomalies(stream) == []          # off by default
    found = goodput.find_anomalies(stream, mfu_min=0.5)  # 1 flop: ~0 MFU
    assert [f["kind"] for f in found] == ["low_mfu"]


def test_anomaly_blocked_input_and_blocked_ckpt():
    stream = [
        _rec(1.0, "step", step=1, wall_ms=400.0, input_wait_ms=2.0),
        _rec(2.0, "step", step=2, wall_ms=400.0, input_wait_ms=1800.0),
        # sync save: the whole write blocks the step path (v1: no
        # block_ms, ms is the blocking time)
        _rec(3.0, "ckpt_save", step=2, ms=2500.0),
        # async save: huge span, tiny blocking slice — NOT flagged
        _rec(4.0, "ckpt_save", step=4, ms=9000.0, block_ms=40.0,
             async_write=True),
        _rec(9.0, "run_end", final_step=4, wall_s=9.0, goodput={}),
    ]
    kinds = sorted(f["kind"] for f in goodput.find_anomalies(stream))
    assert kinds == ["blocked_ckpt", "blocked_input"]
    blocked = {f["kind"]: f for f in goodput.find_anomalies(stream)}
    assert blocked["blocked_input"]["step"] == 2
    assert blocked["blocked_ckpt"]["step"] == 2  # the sync one, not async
    # The threshold is policy: raising it past both clears the findings.
    assert goodput.find_anomalies(stream, blocked_ms=3000.0) == []


def test_anomaly_goodput_invariant_sums_to_wall():
    def run_end(buckets, wall):
        return _rec(10.0, "run_end", final_step=2, wall_s=wall,
                    goodput={"wall_s": wall, "buckets": buckets})

    ok = {"init": 1.0, "compile": 2.0, "productive": 3.0, "input": 0.5,
          "ckpt": 0.5, "eval": 0.0, "stall": 0.0, "other": 3.0}
    assert goodput.find_anomalies([run_end(ok, 10.0)]) == []
    # A lost slice (other dropped a second) violates the partition and
    # is flagged, never silently renormalized.
    bad = dict(ok, other=2.0)
    found = goodput.find_anomalies([run_end(bad, 10.0)])
    assert [f["kind"] for f in found] == ["goodput_invariant"]
    assert found[0]["bucket_sum_s"] == pytest.approx(9.0)
    # run_end with no buckets at all (crashed mid-write): not flagged
    # here — no_run_end and the reconstruction path own that case.
    assert goodput.find_anomalies(
        [_rec(1.0, "run_end", final_step=0, wall_s=5.0, goodput={})]) == []


def test_from_events_v2_input_and_async_block_reconstruction():
    # Crashed attempt (no run_end), schema-2 records: input_wait_ms
    # accumulates into the input bucket, and an async ckpt_save charges
    # only its block_ms — the upload tail overlapped training and must
    # not be billed to ckpt.
    stream = [
        _rec(0.0, "step", step=1, wall_ms=5000.0, input_wait_ms=1000.0),
        _rec(10.0, "step", step=2, wall_ms=500.0, input_wait_ms=250.0),
        _rec(11.0, "step", step=3, wall_ms=500.0, input_wait_ms=250.0),
        _rec(12.0, "ckpt_save", step=3, ms=6000.0, block_ms=100.0,
             async_write=True),
        _rec(20.0, "step", step=4, wall_ms=500.0),  # v1 record: no wait
    ]
    b = goodput.from_events(stream)["buckets"]
    assert b["input"] == pytest.approx(1.5)
    assert b["ckpt"] == pytest.approx(0.1)
    assert b["compile"] == pytest.approx(5.0)
    assert b["productive"] == pytest.approx(1.5)
    # v1 async save without block_ms: blocking unknown, charged as 0 —
    # a v1 sync save still charges its full ms.
    v1 = [_rec(0.0, "step", step=1, wall_ms=1000.0),
          _rec(5.0, "ckpt_save", step=1, ms=2000.0, async_write=True),
          _rec(9.0, "ckpt_save", step=1, ms=2000.0)]
    assert goodput.from_events(v1)["buckets"]["ckpt"] == pytest.approx(2.0)


def test_async_ckpt_sample_is_schema2_with_input_bucket():
    # The shipped async-checkpoint sample run: schema 2 end to end,
    # validating alongside the schema-1 main sample (ACCEPTED_SCHEMAS
    # spans both), with the input bucket populated and the async save's
    # block_ms << ms.
    sample = str(pathlib.Path(_SAMPLES) / "async_ckpt")
    files = events.event_files(sample)
    assert files and events.validate_files(files) == []
    merged = events.merge(sample)
    assert all(r["schema"] == 2 for r in merged)
    s = goodput.from_events(merged)
    assert s["buckets"]["input"] > 0
    assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"],
                                                       abs=0.05)
    save = next(r for r in merged if r["type"] == "ckpt_save")
    assert save["async_write"] and save["ms"] > 10 * save["block_ms"]
    kinds = [f["kind"] for f in goodput.find_anomalies(merged)]
    assert kinds == ["blocked_input"]  # the deliberately starved step 6


# ---------------------------------------------------------------------------
# obs v2: devmem telemetry (no-op on CPU), heartbeat events, counters.
# ---------------------------------------------------------------------------

def test_devmem_noop_on_cpu():
    assert devmem.sample() is None  # CPU backend exposes no memory_stats
    emitted = []
    s = devmem.DevmemSampler(interval_s=0.01,
                             emit_fn=lambda **kw: emitted.append(kw))
    s.start()
    assert not s.active and s._thread is None  # stays inert: zero overhead
    s.stop()
    assert s.peak_summary() == {} and emitted == []


def test_devmem_sampler_peak_tracking():
    # Drive _record directly with synthetic stats — the TPU-side math.
    s = devmem.DevmemSampler(interval_s=60.0, emit_fn=lambda **kw: None)
    s._record([{"id": 0, "peak_bytes_in_use": 100, "bytes_in_use": 90},
               {"id": 1, "peak_bytes_in_use": 300}])
    s._record([{"id": 0, "peak_bytes_in_use": 200}])
    assert s.peak_summary() == {"peak_hbm_bytes": 300,
                                "per_device": {"0": 200, "1": 300}}


def test_heartbeat_structured_stall_event_and_rearm(tmp_path, monkeypatch):
    monkeypatch.setenv(events.ENV_DIR, str(tmp_path))
    log = events.init()
    h = Heartbeat(timeout_s=0.08, poll_s=0.02)
    h.start()
    try:
        deadline = time.monotonic() + 5.0
        while h.stall_count < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert h.stall_count == 1 and h.stalled
        h.beat(7)  # recovery re-arms the watchdog...
        assert not h.stalled
        while h.stall_count < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert h.stall_count == 2  # ...so a second stall reports again
    finally:
        h.stop()
        events.close()
    stalls = [r for r in events.read_file(log.path)
              if r["type"] == "stall"]
    assert len(stalls) == 2
    assert stalls[0]["last_step"] == 0 and stalls[1]["last_step"] == 7
    assert stalls[1]["stall_count"] == 2
    assert all(events.validate_record(r) == [] for r in stalls)


def test_counters_reset_and_bump_tolerance():
    obs_metrics.counters_reset()
    try:
        obs_metrics.bump("x.y")
        obs_metrics.bump("x.y", 2)
        obs_metrics.bump("x.y", "3")       # coerced
        obs_metrics.bump("x.y", object())  # swallowed, never raises
        obs_metrics.bump("z.w")
        assert obs_metrics.counters()["x.y"] == 6
        obs_metrics.counters_reset("x.")
        assert "x.y" not in obs_metrics.counters()
        assert obs_metrics.counters()["z.w"] == 1
    finally:
        obs_metrics.counters_reset()


# ---------------------------------------------------------------------------
# obs v2: the analyzer CLI.
# ---------------------------------------------------------------------------

def test_obs_cli_summarize_samples(capsys):
    from tpuframe.obs.__main__ import main as obs_main

    assert obs_main(["summarize", _SAMPLES]) == 0
    out = capsys.readouterr().out
    assert "goodput breakdown" in out
    assert "restart-lost" in out
    assert "mfu_productive" in out
    assert "peak HBM" in out
    assert "compile_cache.hits = 1" in out


def test_obs_cli_selfcheck_and_anomalies(tmp_path, capsys):
    from tpuframe.obs.__main__ import main as obs_main

    assert obs_main(["summarize", "--selfcheck"]) == 0
    # The sample log contains a stall + a crashed attempt: anomalies is
    # scriptable and exits 1.
    assert obs_main(["anomalies", _SAMPLES]) == 1
    out = capsys.readouterr().out
    assert "[stall]" in out and "[no_run_end]" in out
    # --blocked-ms is plumbed through: past the async sample's starved
    # step (1350 ms) the scan comes back clean.
    async_sample = str(pathlib.Path(_SAMPLES) / "async_ckpt")
    assert obs_main(["anomalies", async_sample]) == 1
    assert "[blocked_input]" in capsys.readouterr().out
    assert obs_main(["anomalies", async_sample, "--blocked-ms",
                     "2000"]) == 0
    merged = tmp_path / "merged.jsonl"
    assert obs_main(["merge", _SAMPLES, "-o", str(merged)]) == 0
    lines = [json.loads(l) for l in merged.read_text().splitlines()]
    assert lines == events.merge(_SAMPLES)


def test_obs_cli_empty_dir_exits_2(tmp_path):
    from tpuframe.obs.__main__ import main as obs_main

    with pytest.raises(SystemExit) as exc:
        obs_main(["summarize", str(tmp_path)])
    assert exc.value.code == 2


# ---------------------------------------------------------------------------
# obs v2: the event stream through the real harness (acceptance shape).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_event_stream_through_harness(tmp_path):
    evdir = str(tmp_path / "events")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": env.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4",
        "TPUFRAME_EVENTS_DIR": evdir,
    })
    out = subprocess.run(
        [sys.executable, "-m", "tpuframe.train", "--config", "smoke",
         "--set", "total_steps=6", "--set", "log_every=3",
         "--set", "eval_every=6", "--set", "eval_batches=1",
         "--set", "global_batch=16"],
        env=env, capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-1500:]

    files = events.event_files(evdir)
    assert len(files) == 1
    assert events.validate_files(files) == [], events.validate_files(files)
    merged = events.merge(evdir)
    types = {r["type"] for r in merged}
    assert {"run_start", "step", "run_end"} <= types
    start = next(r for r in merged if r["type"] == "run_start")
    assert start["flops_per_step"] > 0 and start["devices"] == 4
    assert len([r for r in merged if r["type"] == "step"]) == 6

    s = goodput.from_events(merged)
    assert s["steps"] == 6 and s["final_step"] == 6
    assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"],
                                                       abs=0.02)
    assert s.get("mfu_productive", 0) > 0
    end = next(r for r in merged if r["type"] == "run_end")
    assert end["goodput"]["buckets"]["productive"] > 0
