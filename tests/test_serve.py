"""tpuframe.serve: KV-cache engine, continuous batching, and its gates.

Covers the PR's contracts end to end on the 8-device virtual CPU mesh:

  - golden-logits parity: prefill-then-decode == the training forward,
    position by position, for every prompt bucket (full + ragged)
  - kv_cache shape-bucket invariants and env > DB > default resolution
  - scheduler admit/retire semantics over a fake engine (fast) and the
    loadgen loop over the real AOT engine
  - persistent compile-cache warm restarts for the serving executables
    (miss on first build, hits after jax.clear_caches())
  - TF109: no jit/.apply above the engine seam (positive + negative)
  - zero-collective HLO audit of plain-DP serving decode
  - decode roofline census: compiled cost_analysis bytes bracketed by
    the analytic model (the tune sweep's scoring basis)
  - obs: serve_* event schema + TTFT/TPOT/tokens-per-sec analytics
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models.transformer_lm import LMConfig, TransformerLM
from tpuframe.serve import kv_cache as kv
from tpuframe.serve.scheduler import Request, Scheduler

TINY = LMConfig.tiny()


def _decode_compiled(cfg, slots, capacity, donate=True):
    """AOT-compile the decode step the way the engine does (helper for
    the census tests — no full engine build needed)."""
    from tpuframe.serve import engine as engine_lib

    spec = kv.spec_for_model(cfg, slots=slots, capacity=capacity)
    decode_fn = engine_lib.make_decode_fn(TransformerLM(cfg))
    variables = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
    sds = jax.ShapeDtypeStruct
    p_sds = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         variables["params"])
    dtype = jnp.dtype(spec.dtype)
    cache_sds = tuple((sds(spec.layer_shape(), dtype),
                       sds(spec.layer_shape(), dtype))
                      for _ in range(cfg.num_layers))
    jitted = jax.jit(decode_fn, donate_argnums=(1, 2, 3) if donate else ())
    compiled = jitted.lower(p_sds, sds((slots, 1), jnp.int32),
                            sds((slots,), jnp.int32), cache_sds).compile()
    param_bytes = sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree_util.tree_leaves(variables["params"]))
    return compiled, spec, param_bytes


# ---------------------------------------------------------------------------
# kv_cache: shape buckets + spec invariants
# ---------------------------------------------------------------------------

class TestKVCache:
    def test_spec_shapes_and_bytes(self):
        spec = kv.spec_for_model(TINY, slots=4, capacity=64)
        # a cached token is a column: the capacity axis is minor
        assert spec.layer_shape() == (4, TINY.num_heads, TINY.head_dim, 64)
        # K + V, all layers, f32
        assert spec.bytes_per_token() == \
            2 * TINY.num_layers * TINY.num_heads * TINY.head_dim * 4
        assert spec.total_bytes() == 4 * 64 * spec.bytes_per_token()

    def test_spec_rejects_unaligned_capacity(self):
        with pytest.raises(ValueError, match="multiple of"):
            kv.spec_for_model(TINY, slots=4, capacity=65)

    def test_init_cache(self):
        spec = kv.spec_for_model(TINY, slots=2, capacity=16)
        layers, lengths = kv.init_cache(spec)
        assert len(layers) == TINY.num_layers
        assert layers[0][0].shape == spec.layer_shape()
        assert lengths.shape == (2,) and int(lengths.sum()) == 0

    def test_bucket_for(self):
        assert kv.bucket_for(1, (16, 32)) == 16
        assert kv.bucket_for(16, (16, 32)) == 16
        assert kv.bucket_for(17, (16, 32)) == 32
        with pytest.raises(ValueError, match="admission"):
            kv.bucket_for(33, (16, 32))

    def test_capacity_for_rounds_to_block(self):
        assert kv.capacity_for(1, 16) == 16
        assert kv.capacity_for(16, 16) == 16
        assert kv.capacity_for(17, 16) == 32

    def test_parse_buckets(self):
        assert kv.parse_buckets("64,128, 256") == (64, 128, 256)
        assert kv.parse_buckets("256;64") == (64, 256)
        with pytest.raises(ValueError):
            kv.parse_buckets("12")

    def test_check_buckets(self):
        assert kv.check_buckets((16, 32), 32) == []
        assert kv.check_buckets((32, 16), 32)      # unsorted
        assert kv.check_buckets((16, 64), 32)      # bucket > capacity

    def test_resolution_env_beats_db_and_default(self, monkeypatch):
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
        monkeypatch.delenv("TPUFRAME_SERVE_BUCKETS", raising=False)
        monkeypatch.delenv("TPUFRAME_DECODE_BLOCK", raising=False)
        assert kv.resolve_buckets() == kv.DEFAULT_PROMPT_BUCKETS
        assert kv.resolve_decode_block() == kv.DEFAULT_DECODE_BLOCK
        monkeypatch.setenv("TPUFRAME_SERVE_BUCKETS", "32,96")
        monkeypatch.setenv("TPUFRAME_DECODE_BLOCK", "32")
        assert kv.resolve_buckets() == (32, 96)
        assert kv.resolve_decode_block() == 32

    def test_resolution_db_tier_under_generation(self, monkeypatch,
                                                 tmp_path):
        db_path = tmp_path / "db.json"
        db_path.write_text(json.dumps({
            "version": 1, "records": [{
                "program": "serve_decode_test", "family": "serve_lm",
                "fingerprint": "ab" * 16, "topology": "v5e:2x2",
                "generation": "v5e",
                "config": {"decode_block": 64,
                           "prompt_buckets": [64, 256], "slots": 8},
                "predicted": {"predicted_ms": 0.05}}]}))
        monkeypatch.setenv("TPUFRAME_TUNE_DB", str(db_path))
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.delenv("TPUFRAME_SERVE_BUCKETS", raising=False)
        monkeypatch.delenv("TPUFRAME_DECODE_BLOCK", raising=False)
        assert kv.resolve_decode_block() == 64
        assert kv.resolve_buckets() == (64, 256)
        # plain run (no generation): DB must NOT engage
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
        assert kv.resolve_decode_block() == kv.DEFAULT_DECODE_BLOCK


# ---------------------------------------------------------------------------
# Golden-logits parity — the tentpole's correctness contract.
# ---------------------------------------------------------------------------

class TestGoldenParity:
    def test_parity_every_bucket(self):
        from tpuframe.serve.engine import golden_parity_check

        buckets = (16, 32)
        capacity = kv.capacity_for(max(buckets) + 4, 16)
        problems = golden_parity_check(TINY, buckets=buckets,
                                       capacity=capacity, decode_tokens=4)
        assert problems == []

    def test_parity_detects_capacity_overrun(self):
        from tpuframe.serve.engine import golden_parity_check

        problems = golden_parity_check(TINY, buckets=(32,), capacity=32,
                                       decode_tokens=4)
        assert any("exceeds capacity" in p for p in problems)

    def test_ring_wraparound_is_sliding_window(self):
        """Past capacity the ring overwrites the oldest entries: lengths
        keep counting, valid clamps at capacity, and decode still runs
        (numerics = sliding-window attention, not a fault)."""
        cfg = TINY
        capacity = 8
        model = TransformerLM(cfg)
        ids = jax.random.randint(jax.random.key(0), (1, 14), 0,
                                 cfg.vocab_size)
        params = model.init(jax.random.key(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        shape = kv.spec_for_model(cfg, slots=1,
                                  capacity=capacity).layer_shape()
        layers = tuple((jnp.zeros(shape), jnp.zeros(shape))
                       for _ in range(cfg.num_layers))
        _, layers = model.apply({"params": params}, ids[:, :8],
                                kv_cache=layers,
                                cache_length=jnp.zeros((1,), jnp.int32))
        length = jnp.asarray([8], jnp.int32)
        for t in range(8, 14):  # 6 decode steps, wrapping the ring
            logits, layers = model.apply(
                {"params": params}, ids[:, t:t + 1], kv_cache=layers,
                cache_length=length, decode=True)
            length = length + 1
        assert np.isfinite(np.asarray(logits)).all()


# ---------------------------------------------------------------------------
# Scheduler semantics over a fake engine (no compiles — fast tier).
# ---------------------------------------------------------------------------

class _FakeEngine:
    """Slot bookkeeping without jax: prefill echoes, decode counts up."""

    def __init__(self, slots=2, buckets=(8, 16), eos_id=None):
        self.slots = slots
        self.prompt_buckets = buckets
        self.capacity = 2 * max(buckets)
        self.eos_id = eos_id
        self._active = {}
        self.released = []

    def prefill(self, prompt):
        return 100 + len(prompt), ("pcache", len(prompt)), len(prompt)

    def insert(self, slot, pcache, length, first_token):
        self._active[slot] = first_token

    def release(self, slot):
        del self._active[slot]
        self.released.append(slot)

    def decode_step(self):
        out = np.zeros(self.slots, np.int32)
        for slot, tok in self._active.items():
            self._active[slot] = tok + 1
            out[slot] = tok + 1
        return out


class TestScheduler:
    def test_admission_rejects_oversized_prompt(self):
        sched = Scheduler(_FakeEngine(buckets=(8,)))
        with pytest.raises(ValueError, match="exceeds largest bucket"):
            sched.submit(Request(rid=0, prompt=list(range(9))))

    def test_continuous_batching_admits_and_retires(self):
        eng = _FakeEngine(slots=2)
        sched = Scheduler(eng)
        for rid in range(5):
            sched.submit(Request(rid=rid, prompt=[1, 2, 3],
                                 max_new_tokens=3))
        steps = 0
        while sched.has_work():
            sched.step()
            steps += 1
            assert steps < 50
        assert len(sched.completed) == 5
        assert [r.rid for r in sched.completed[:2]] == [0, 1]  # FIFO
        for r in sched.completed:
            assert len(r.tokens) == 3
            assert r.ttft_ms() is not None and r.ttft_ms() >= 0
            assert r.tpot_ms() is not None and r.tpot_ms() >= 0
        # a long generation never blocked a short one: more completions
        # than slot count proves slots were recycled mid-run
        assert len(sched.completed) > eng.slots

    def test_eos_retires_early(self):
        # fake decode emits first_token+1, +2, ...: eos = 104 stops rid 0
        # (prompt len 3 -> first token 103) after one decode step.
        eng = _FakeEngine(slots=1, eos_id=104)
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=50))
        while sched.has_work():
            sched.step()
        (req,) = sched.completed
        assert req.tokens[-1] == 104
        assert len(req.tokens) == 2

    def test_retire_then_admit_fills_freed_slot_same_step(self):
        """A slot freed by this step's retire is refilled by the trailing
        admit pass — the follower's prefill (and TTFT clock stop) lands
        this step instead of idling the slot until the next one."""
        sched = Scheduler(_FakeEngine(slots=1))
        sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
        sched.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
        sched.step()       # rid 0: admit + decode = done; rid 1 admitted
        assert [r.rid for r in sched.completed] == [0]
        follower = sched.active[0]
        assert follower is not None and follower.rid == 1
        assert len(follower.tokens) == 1          # prefill token landed
        assert follower.first_token_t is not None  # TTFT already stopped
        sched.step()                      # rid 1's one decode token
        assert [r.rid for r in sched.completed] == [0, 1]
        for r in sched.completed:
            assert len(r.tokens) == 2
            assert r.ttft_ms() is not None and r.ttft_ms() >= 0

    def test_instant_retire_reuses_slot_within_admit_pass(self):
        """max_new_tokens=1 requests finish at prefill: the admit pass
        retires them in place and reuses the slot, so a 1-slot scheduler
        drains any number of them in a single step."""
        sched = Scheduler(_FakeEngine(slots=1))
        for rid in range(3):
            sched.submit(Request(rid=rid, prompt=[rid], max_new_tokens=1))
        produced = sched.step()
        assert produced == 3               # all three admitted this step
        assert not sched.has_work()
        assert [r.rid for r in sched.completed] == [0, 1, 2]
        for r in sched.completed:
            assert len(r.tokens) == 1 and r.done_t is not None


    def test_retire_gives_the_slot_up_on_the_engine(self):
        eng = _FakeEngine(slots=2)
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=3))
        sched.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=2))
        sched.submit(Request(rid=2, prompt=[1], max_new_tokens=1))
        while sched.has_work():
            sched.step()
        # rid 1 leaves slot 1 first; rid 2 finishes at its prefill there
        # and leaves it again; rid 0 leaves slot 0 last
        assert eng.released == [1, 1, 0]
        assert eng._active == {}

    def test_step_counts_the_blocks_the_live_slots_hold(self):
        """``sched.step`` carries ``kv_blocks`` — what the step's decode
        attention finds below the live slots' lengths, in lane blocks —
        and the two counters add it up beside the rings' whole."""
        from tpuframe.obs import metrics, timeline
        from tpuframe.serve.kv_cache import KV_BLOCK

        eng = _FakeEngine(slots=3, buckets=(128, 512))   # capacity 1024
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=[7] * 127, max_new_tokens=4))
        sched.submit(Request(rid=1, prompt=[7] * 300, max_new_tokens=2))
        before = metrics.counters("decode.")
        t = time.monotonic()
        steps = 0
        while sched.has_work():
            sched.step()
            steps += 1
        spans = [s for s in timeline.spans(t0=t) if s.name == "sched.step"]
        # step 1 decodes over 127 + 1 and 300 + 1 columns: 1 + 3 blocks;
        # rid 1 is gone after it, and rid 0's 129th column opens a block
        assert [s.args["kv_blocks"] for s in spans] == [4, 2, 2]
        assert [s.args["active"] for s in spans] == [1, 1, 0]
        after = metrics.counters("decode.")
        live = after["decode.kv_blocks_live"] \
            - before.get("decode.kv_blocks_live", 0)
        ring = after["decode.kv_blocks_ring"] \
            - before.get("decode.kv_blocks_ring", 0)
        assert live == 8
        assert ring == steps * eng.slots * eng.capacity // KV_BLOCK == 72
        # a step with nothing to decode counts nothing
        sched.step()
        assert timeline.last("sched.step").args["kv_blocks"] == 0
        assert metrics.counters("decode.") == after

    def test_kv_blocks_stop_at_the_rings_capacity(self):
        from tpuframe.obs import timeline

        eng = _FakeEngine(slots=1, buckets=(128,))        # capacity 256
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=[7] * 128, max_new_tokens=200))
        seen = set()
        while sched.has_work():
            sched.step()
            seen.add(timeline.last("sched.step").args["kv_blocks"])
        assert seen == {2}      # 129 .. 327 columns asked of a ring of 256


# ---------------------------------------------------------------------------
# The real engine's slots: held lengths, release, idle neighbours.
# ---------------------------------------------------------------------------

class TestReleasedSlots:
    @pytest.fixture(scope="class")
    def engine(self):
        from tpuframe.serve.engine import LMEngine

        return LMEngine(TINY, slots=3, prompt_buckets=(8, 16),
                        decode_block=8, max_context=24,
                        enable_persistent_cache=False)

    @staticmethod
    def _admit(engine, slot, prompt):
        first, pcache, length = engine.prefill(prompt)
        engine.insert(slot, pcache, length, first)
        return first

    def test_release_zeroes_the_length_and_it_stays(self, engine):
        engine.reset()
        self._admit(engine, 0, [3, 1, 4, 1, 5])
        self._admit(engine, 1, [9, 2, 6])
        for _ in range(2):
            engine.decode_step()
        # a slot that never held a request has not moved
        assert np.asarray(engine._lengths).tolist() == [7, 5, 0]
        engine.release(0)
        assert np.asarray(engine._lengths).tolist() == [0, 5, 0]
        for _ in range(3):
            engine.decode_step()
        assert np.asarray(engine._lengths).tolist() == [0, 8, 0]
        assert "release" in engine.compiled_programs()
        with pytest.raises(ValueError, match="out of range"):
            engine.release(3)

    def test_lengths_keep_counting_past_the_capacity(self, engine):
        """Holding idle slots at 0 leaves a live slot's count alone: it
        runs on past the ring's capacity (24), the ring wraps."""
        engine.reset()
        self._admit(engine, 2, list(range(1, 17)))
        for _ in range(12):
            toks = engine.decode_step()
        assert np.asarray(engine._lengths).tolist() == [0, 0, 28]
        assert 0 <= int(toks[2]) < TINY.vocab_size

    def test_a_live_slots_stream_is_the_same_beside_idle_neighbours(
            self, engine):
        prompt = [5, 3, 8, 2, 7, 1]

        def stream(slot, before=None):
            engine.reset()
            if before is not None:
                before()
            toks = [self._admit(engine, slot, prompt)]
            for _ in range(6):
                toks.append(int(engine.decode_step()[slot]))
            return toks

        def a_retired_neighbour():
            # slot 0 held a request for a while and gave it up; slot 2
            # never held one
            self._admit(engine, 0, [4, 4, 4, 4, 4, 4, 4, 4, 4])
            for _ in range(3):
                engine.decode_step()
            engine.release(0)

        alone = stream(0)
        assert stream(1, a_retired_neighbour) == alone
        assert stream(2) == alone

    def test_scheduler_leaves_retired_slots_at_zero(self, engine):
        engine.reset()
        sched = Scheduler(engine)
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
        sched.submit(Request(rid=1, prompt=[4, 5, 6, 7], max_new_tokens=6))
        while sched.has_work():
            sched.step()
            live = [r is not None for r in sched.active]
            lengths = np.asarray(engine._lengths)
            assert [bool(n) for n in lengths] == live
        assert np.asarray(engine._lengths).tolist() == [0, 0, 0]
        assert [len(r.tokens) for r in sched.completed] == [2, 6]


# ---------------------------------------------------------------------------
# The real engine: loadgen, events, compile-cache warm restart.
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestEngineLoadgen:
    def test_loadgen_completes_and_emits_events(self, tmp_path):
        from tpuframe.obs import events as obs_events
        from tpuframe.obs import goodput
        from tpuframe.serve import loadgen
        from tpuframe.serve.engine import LMEngine

        events_dir = tmp_path / "events"
        obs_events.init(str(events_dir))
        try:
            engine = LMEngine(TINY, slots=2, prompt_buckets=(16, 32),
                              decode_block=16, max_context=40,
                              enable_persistent_cache=False)
            reqs = loadgen.synthetic_requests(
                6, buckets=(16, 32), vocab_size=TINY.vocab_size,
                max_new_tokens=4, seed=1)
            stats = loadgen.run_loadgen(engine, reqs)
        finally:
            obs_events.close()
        assert stats["requests"] == 6 and stats["unfinished"] == 0
        assert stats["total_tokens"] == 6 * 4

        merged = obs_events.merge(str(events_dir))
        assert obs_events.validate_files(
            obs_events.event_files(str(events_dir))) == []
        serve = goodput.serve_stats(merged)
        assert serve is not None
        assert serve["requests"] == 6
        assert serve["ttft_ms"] and serve["tpot_ms"]
        assert serve["tokens_per_s"] and serve["tokens_per_s"] > 0
        assert serve["tokens_per_s_per_chip"] == pytest.approx(
            serve["tokens_per_s"] / serve["n_devices"], abs=0.05)
        # training-only logs stay serving-free
        assert goodput.serve_stats(
            [r for r in merged if not r["type"].startswith("serve")]) \
            is None

    def test_persistent_cache_warm_restart(self, tmp_path, monkeypatch,
                                           request):
        """Second engine build after jax.clear_caches() must be served
        from the on-disk compile cache: hits > 0, no new misses beyond
        the first build's."""
        from tpuframe.obs import metrics
        from tpuframe.serve.engine import LMEngine
        from tpuframe.utils import compile_cache

        # The standard variable places the cache (jax read it at import;
        # the test stands in for that).
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        old_dir = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
        request.addfinalizer(lambda: (
            jax.config.update("jax_compilation_cache_dir", old_dir),
            compile_cache.reset_cache()))
        # tiny programs compile in <1s; keep them all
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE_MIN_S", "0")
        compile_cache.enable()
        metrics.reset_counters()

        kw = dict(slots=2, prompt_buckets=(16,), decode_block=16,
                  max_context=24)
        LMEngine(TINY, **kw)
        first = metrics.counters("compile_cache")
        assert first.get("compile_cache.misses", 0) > 0

        jax.clear_caches()
        compile_cache.reset_cache()
        LMEngine(TINY, **kw)
        second = metrics.counters("compile_cache")
        # every program the first engine compiled is served from disk;
        # unrelated tiny ops recompiled by clear_caches() may still miss
        # (they predate enable()), so only the hit floor is asserted
        assert second.get("compile_cache.hits", 0) >= \
            first.get("compile_cache.misses", 0)

    def test_bert_single_shot(self):
        from tpuframe.models.bert import BertConfig
        from tpuframe.serve.engine import BertClassifier

        clf = BertClassifier(BertConfig.tiny(num_classes=3),
                             buckets=(16, 32))
        label, probs = clf.classify(list(range(1, 11)))
        assert 0 <= label < 3
        assert probs.shape == (3,)
        assert float(probs.sum()) == pytest.approx(1.0, abs=1e-4)
        # identical request in the other bucket: same model, same answer
        label2, _ = clf.classify(list(range(1, 20)))
        assert 0 <= label2 < 3


# ---------------------------------------------------------------------------
# TF109 lint: the compile seam is enforced, not a convention.
# ---------------------------------------------------------------------------

class TestTF109:
    BAD = ("import jax\n\n"
           "def serve_one(model, params, ids, fn):\n"
           "    step = jax.jit(fn)\n"
           "    out = model.apply({'params': params}, ids)\n"
           "    return step, out\n")

    def test_fires_above_the_seam(self):
        from tpuframe.analysis import source_lint

        findings = source_lint.lint_source(
            self.BAD, "tpuframe/serve/scheduler.py")
        assert sum(f.rule == "TF109" for f in findings) == 2  # jit + apply

    def test_engine_is_the_sanctioned_seam(self):
        from tpuframe.analysis import source_lint

        findings = source_lint.lint_source(
            self.BAD, "tpuframe/serve/engine.py")
        assert not [f for f in findings if f.rule == "TF109"]

    def test_non_serve_paths_unaffected(self):
        from tpuframe.analysis import source_lint

        findings = source_lint.lint_source(
            self.BAD, "tpuframe/parallel/step.py")
        assert not [f for f in findings if f.rule == "TF109"]

    def test_shipped_serve_package_is_clean(self):
        import tpuframe.serve as serve_pkg
        from tpuframe.analysis import source_lint

        pkg_dir = os.path.dirname(serve_pkg.__file__)
        findings = source_lint.lint_paths([pkg_dir])
        assert not [str(f) for f in findings if f.rule == "TF109"]

    def test_serve_check_gate(self):
        from tpuframe import serve

        assert serve.check() == []


# ---------------------------------------------------------------------------
# Zero-collective serving decode (plain DP) — budget + HLO audit.
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestServeDecodeAudit:
    def test_budget_forbids_all_collectives(self):
        from tpuframe.analysis import budgets

        b = budgets.serve_decode_budget(12345)
        assert b.allowed == {}
        assert budgets.strategy_budget("serve-dp-decode",
                                       param_bytes=0).name \
            == "serve-dp-decode"

    def test_dp_decode_audit_passes(self):
        from tpuframe.analysis import strategies

        audit = strategies.audit_strategy("serve-dp-decode", 8)
        if audit.status == "unavailable":
            pytest.skip(audit.reason)
        assert audit.status == "ok", audit.violations
        # nothing above the scalar floor: every surviving op is tiny
        # index/length bookkeeping, not tensor traffic
        for op in audit.report.ops:
            assert op.bytes < audit.budget.ignore_below
        # the checked-in auto-derived budget is this program's exact
        # record — asserted instead of hand-copied byte constants
        import jax

        from tpuframe.analysis import shardflow

        derived_file = shardflow.load_derived()
        assert derived_file is not None
        if derived_file["jax"] == jax.__version__:
            assert shardflow.derive_budget(
                audit.report, audit.budget.ignore_below) == \
                shardflow.derived_for("serve-dp-decode")


# ---------------------------------------------------------------------------
# Decode roofline census: analytic model vs compiled cost_analysis.
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestDecodeRooflineCensus:
    def test_analytic_brackets_compiled_bytes(self):
        """The analytic decode model (params + KV read) must be a LOWER
        bound on the compiled program's byte count, and within 3x of it:
        the compiled count adds the donated cache write-back and the
        attention intermediates (observed ratio ~1.9x for the tiny
        config on this backend)."""
        from tpuframe.tune import roofline

        compiled, spec, param_bytes = _decode_compiled(TINY, 4, 64)
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        got = float((ca or {}).get("bytes accessed", 0.0))
        if got <= 0:
            pytest.skip("backend reports no cost analysis")
        analytic = roofline.decode_score(
            param_bytes=param_bytes,
            kv_bytes_per_token=spec.bytes_per_token(),
            slots=4, context=64)
        assert analytic.bytes_per_step <= got <= 3 * analytic.bytes_per_step
        assert analytic.bound == "hbm"

    def test_compiled_bytes_scale_with_kv_capacity(self):
        """Doubling KV capacity must grow compiled bytes by at least the
        extra cache read and at most ~5x it (write-back + attention
        intermediates; observed ~3.3x)."""
        c64, spec, _ = _decode_compiled(TINY, 4, 64)
        c128, _, _ = _decode_compiled(TINY, 4, 128)

        def _bytes(c):
            ca = c.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return float((ca or {}).get("bytes accessed", 0.0))

        b64, b128 = _bytes(c64), _bytes(c128)
        if b64 <= 0 or b128 <= 0:
            pytest.skip("backend reports no cost analysis")
        kv_delta = 4 * 64 * spec.bytes_per_token()
        assert kv_delta <= (b128 - b64) <= 5 * kv_delta

    def test_decode_score_properties(self):
        from tpuframe.tune import roofline

        s = roofline.decode_score(param_bytes=50e6,
                                  kv_bytes_per_token=4096, slots=8,
                                  context=1024)
        # more slots amortize the weight read: higher per-chip throughput
        s2 = roofline.decode_score(param_bytes=50e6,
                                   kv_bytes_per_token=4096, slots=16,
                                   context=1024)
        assert s2.tokens_per_s_per_chip > s.tokens_per_s_per_chip
        # longer context adds KV traffic: lower throughput
        s3 = roofline.decode_score(param_bytes=50e6,
                                   kv_bytes_per_token=4096, slots=8,
                                   context=4096)
        assert s3.tokens_per_s_per_chip < s.tokens_per_s_per_chip
        with pytest.raises(ValueError):
            roofline.decode_score(param_bytes=1, kv_bytes_per_token=1,
                                  slots=0, context=1)


# ---------------------------------------------------------------------------
# Obs: event schema + analyzer stats.
# ---------------------------------------------------------------------------

class TestServeObs:
    def test_required_fields_registered(self):
        from tpuframe.obs import events

        for etype in ("serve_step", "serve_request", "serve_summary"):
            assert etype in events.REQUIRED_FIELDS

    def test_serve_stats_from_synthetic_events(self):
        from tpuframe.obs import goodput

        events = [
            {"type": "serve_request", "id": i, "prompt_tokens": 10,
             "output_tokens": 4, "ttft_ms": 10.0 + i, "tpot_ms": 2.0}
            for i in range(10)
        ] + [{"type": "serve_summary", "requests": 10, "tokens_per_s": 80.0,
              "n_devices": 4}]
        s = goodput.serve_stats(events)
        assert s["requests"] == 10
        assert s["ttft_ms"]["p50"] == pytest.approx(15.0, abs=1.01)
        assert s["tpot_ms"]["p99"] == 2.0
        assert s["tokens_per_s_per_chip"] == 20.0
        assert s["n_devices"] == 4

    def test_serve_stats_none_without_serving(self):
        from tpuframe.obs import goodput

        assert goodput.serve_stats(
            [{"type": "step", "step": 1, "wall_ms": 5.0}]) is None

    def test_serve_stats_reconstructs_without_summary(self):
        from tpuframe.obs import goodput

        events = [{"type": "serve_step", "step": i, "wall_ms": 10.0,
                   "active": 2, "admitted": 0, "produced": 2}
                  for i in range(5)]
        s = goodput.serve_stats(events)
        assert s["tokens_per_s"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# Tune: serve_lm sweep plumbing (pure parts — the sweep itself is the
# offline CLI's job and its artifacts are committed).
# ---------------------------------------------------------------------------

class TestServeTune:
    def test_serve_bucket_sets(self):
        from tpuframe.tune import search

        buckets, capacity = search.serve_bucket_sets(64)
        assert capacity == 256
        assert buckets == (64, 128, 256)
        assert kv.check_buckets(buckets, capacity) == []

    def test_committed_db_has_serve_family(self):
        from tpuframe.tune import db as tune_db

        path = tune_db.default_db_path()
        if not os.path.exists(path):
            pytest.skip("no committed tuning DB")
        db = tune_db.TuningDB.open(path)
        recs = db.records(family="serve_lm")
        assert recs, "tune_db.json lost its serve_lm family"
        best = db.best(family="serve_lm", generation="v5e")
        assert "decode_block" in best.config
        assert best.config.get("prompt_buckets")
        env = best.env_overrides()
        assert "TPUFRAME_DECODE_BLOCK" in env
        assert "TPUFRAME_SERVE_BUCKETS" in env

    def test_committed_serve_report(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(root, "perf", "results",
                            "serve_report_v5e_22.json")
        if not os.path.exists(path):
            pytest.skip("no committed serve report")
        with open(path) as f:
            report = json.load(f)
        assert report["winner"] is not None
        rows = report["serve"]["rows"]
        assert rows == sorted(rows,
                              key=lambda r: r["predicted_ms_per_token"])


# ---------------------------------------------------------------------------
# Replica drain semantics (serve/replica.py over the fake engine).
# ---------------------------------------------------------------------------

class TestReplicaDrain:
    def test_drain_finishes_inflight_then_exits(self):
        """A replica that flips draining mid-generation still answers
        every request it already accepted (200 with the full token
        stream), rejects new work with 503, reads unhealthy for the
        router's scrape — and only then does its main loop exit."""
        import threading

        from tpuframe.serve.replica import FakeEngine, Replica

        replica = Replica(FakeEngine(slots=1), handler_timeout_s=10.0)
        results = []

        def call(rid):
            body = json.dumps({"rid": rid, "prompt": [1, 2, 3],
                               "max_new_tokens": 4}).encode()
            results.append(replica.handle_generate(body))

        t = threading.Thread(target=call, args=(0,), daemon=True)
        t.start()
        deadline = 200
        while not replica._inbox and deadline:  # accepted, not yet pumped
            deadline -= 1
            import time as _time
            _time.sleep(0.01)
        assert replica._inbox, "request never reached the inbox"

        replica.drain()                      # mid-generation drain signal
        assert replica.healthy() is False    # /healthz now reads 503
        status, body = replica.handle_generate(
            json.dumps({"rid": 1, "prompt": [4], "max_new_tokens": 2})
            .encode())
        assert status == 503                 # new work rejected
        assert json.loads(body.decode())["error"] == "draining"

        rc = replica.run()                   # drains, then exits
        assert rc == 0
        t.join(5.0)
        (accepted,) = results                # the accepted request: 200,
        status, body = accepted              # full stream, never dropped
        assert status == 200
        msg = json.loads(body.decode())
        assert msg["rid"] == 0 and len(msg["tokens"]) == 4
        assert msg["ttft_ms"] is not None
        assert not replica.scheduler.has_work()

    def test_fake_engine_streams_are_prompt_deterministic(self):
        """Re-prefilling the same prompt on a fresh replica reproduces
        the identical token stream — the idempotence the router's
        hedging and redispatch (first-winner-kept) rely on."""
        from tpuframe.serve.replica import FakeEngine

        def stream(prompt, n):
            sched = Scheduler(FakeEngine(slots=1))
            sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=n))
            while sched.has_work():
                sched.step()
            return sched.completed[0].tokens

        assert stream([5, 6, 7], 6) == stream([5, 6, 7], 6)
        assert stream([5, 6, 7], 6) != stream([5, 6, 8], 6)

    def test_bad_request_and_oversized_prompt_get_400(self):
        from tpuframe.serve.replica import FakeEngine, Replica

        replica = Replica(FakeEngine(slots=1))
        status, _ = replica.handle_generate(b"not json")
        assert status == 400
        status, body = replica.handle_generate(
            json.dumps({"rid": 0, "prompt": list(range(100)),
                        "max_new_tokens": 2}).encode())
        assert status == 400                # outside buckets: rejected
        assert "outside buckets" in json.loads(body.decode())["error"]
