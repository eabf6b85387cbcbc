"""tpuframe.tune fast tier (CPU, no TPU topology compile — the AOT sweep
itself is exercised by ``python -m tpuframe.tune sweep``):

  - roofline tables reproduce PERF.md §2's recorded ResNet-50 b=512
    anchors (1.252e13 flops / 1.435e11 bytes -> 63.6 ms MXU / 177 ms HBM,
    bandwidth-bound);
  - flash-attention block candidates exceeding the Mosaic VMEM
    double-buffer budget are pruned BEFORE any compile;
  - tuning-DB round-trip, predicted->measured upgrade, fingerprint
    mismatch fallback, env-beats-DB precedence;
  - a seeded compiler-option set changes the program fingerprint;
  - the shared compile-cache helper records persistent-cache hits in
    obs.metrics (the warm-restart path PR 2's relaunch loop exercises).
"""

import json
import os

import pytest

from tpuframe.tune import db as tune_db
from tpuframe.tune import roofline
from tpuframe.tune.search import (DEFAULT_VMEM_BUDGET, fa_block_candidates,
                                  fa_vmem_bytes, xla_opts_candidate_sets)


class TestRoofline:
    def test_resnet50_b512_anchors(self):
        # PERF.md §2: "t_mxu = 1.252e13 / 197e12 = 63.6 ms",
        # "t_hbm = 1.435e11 / 8.1e11 = 177.2 ms" — bandwidth-bound.
        s = roofline.score("v5e", flops=1.252e13, bytes_accessed=1.435e11)
        assert s["t_mxu_ms"] == pytest.approx(63.6, abs=0.1)
        assert s["t_hbm_ms"] == pytest.approx(177.2, abs=0.1)
        assert s["bound"] == "hbm"
        assert s["predicted_ms"] == s["t_hbm_ms"]

    def test_fits_verdict(self):
        s = roofline.score("v5e", flops=1e12, bytes_accessed=1e9,
                           peak_memory_bytes=20e9)
        assert s["fits"] is False  # v5e HBM is 15.75 GB
        s = roofline.score("v5e", flops=1e12, bytes_accessed=1e9,
                           peak_memory_bytes=10e9)
        assert s["fits"] is True
        s = roofline.score("v5e", flops=1e12, bytes_accessed=1e9)
        assert s["fits"] is None

    def test_scan_caveat_tags_lower_bound(self):
        # §8: scan bodies are counted once; byte scores of
        # scan-containing programs are lower bounds, and the tag must
        # survive into the score dict.
        s = roofline.score("v5e", flops=1e12, bytes_accessed=1e9,
                           contains_scan=True)
        assert s["bytes_lower_bound"] is True
        assert roofline.contains_scan("  %x = while(...)")
        assert not roofline.contains_scan("  %x = fusion(...)")

    def test_generation_table(self):
        # peak-flops column must agree with bench.py's BF16_PEAK_FLOPS
        assert roofline.get_hardware("v4").bf16_flops == 275e12
        assert roofline.get_hardware("v5e").bf16_flops == 197e12
        assert roofline.get_hardware("v5p").bf16_flops == 459e12
        assert roofline.get_hardware("v6e").bf16_flops == 918e12
        assert roofline.get_hardware("v5e:2x2").generation == "v5e"
        with pytest.raises(KeyError):
            roofline.get_hardware("v99")

    def test_check_tables_clean(self):
        assert roofline.check_tables() == []

    def test_score_compiled_list_shaped_cost_analysis(self):
        # older jax returns one cost dict PER DEVICE from cost_analysis()
        class FakeCompiled:
            def cost_analysis(self):
                return [{"flops": 1.252e13, "bytes accessed": 1.435e11}]

            def memory_analysis(self):
                raise RuntimeError("unavailable")

            def as_text(self):
                return "ENTRY main { fusion }"

        s = roofline.score_compiled(FakeCompiled(), "v5e")
        assert s["bound"] == "hbm"
        assert s["t_hbm_ms"] == pytest.approx(177.2, abs=0.1)
        assert s["fits"] is None

    def test_mxu_bound_verdict(self):
        # plenty of flops, almost no bytes -> compute-bound
        s = roofline.score("v5e", flops=1e14, bytes_accessed=1e6)
        assert s["bound"] == "mxu" and s["predicted_ms"] == s["t_mxu_ms"]


class TestVmemPruning:
    def test_default_grid_fits_at_d64(self):
        # the production grid (seq 2048, d 64, blocks {128,256,512}^2)
        # is entirely within budget — nothing to prune
        kept, pruned = fa_block_candidates(2048, 64)
        assert len(kept) == 9 and pruned == []

    def test_over_budget_pruned_before_compile(self):
        # (2048, 2048) at d=256 double-buffers to 20 MiB > 16 MiB: the
        # §11 class of tiling the real compiler rejects must die here,
        # not in a compile error
        assert fa_vmem_bytes(2048, 2048, 256) > DEFAULT_VMEM_BUDGET
        kept, pruned = fa_block_candidates(2048, 256, blocks=(128, 2048))
        reasons = {(p["fa_block_q"], p["fa_block_k"]): p["pruned"]
                   for p in pruned}
        assert reasons == {(2048, 2048): "vmem_over_budget"}
        assert {(c["fa_block_q"], c["fa_block_k"]) for c in kept} == {
            (128, 128), (128, 2048), (2048, 128)}

    def test_explicit_budget(self):
        kept, pruned = fa_block_candidates(2048, 64,
                                           budget=1024 * 1024)
        # 0.75 MiB (128x128) survives a 1 MiB budget; 256x256 (1.5 MiB)
        # and up do not
        assert {(c["fa_block_q"], c["fa_block_k"]) for c in kept} == {
            (128, 128)}
        assert all(p["pruned"] == "vmem_over_budget" for p in pruned)

    def test_indivisible_seq_pruned(self):
        _, pruned = fa_block_candidates(2048, 64, blocks=(128, 768))
        assert {(p["fa_block_q"], p["fa_block_k"]) for p in pruned} == {
            (128, 768), (768, 128), (768, 768)}
        assert all(p["pruned"] == "seq_not_divisible" for p in pruned)

    def test_vmem_model_monotone(self):
        # doubling either block dimension must not shrink the footprint
        assert fa_vmem_bytes(256, 128, 64) > fa_vmem_bytes(128, 128, 64)
        assert fa_vmem_bytes(128, 256, 64) > fa_vmem_bytes(128, 128, 64)
        assert fa_vmem_bytes(128, 128, 256) > fa_vmem_bytes(128, 128, 64)

    def test_lane_padding_floors_head_dim(self):
        # d=64 pads to 128 lanes: halving head_dim below 128 cannot
        # halve VMEM (the §11 padded-byte rule)
        assert fa_vmem_bytes(128, 128, 64) == fa_vmem_bytes(128, 128, 128)


def _rec(program="flash_mha_s2048_d64", family="flash_attention",
         gen="v5e", config=None, predicted_ms=10.0, vmem=0, fp="fp0"):
    return {"program": program, "family": family, "fingerprint": fp,
            "topology": "v5e:2x2", "generation": gen,
            "config": config or {"fa_block_q": 128, "fa_block_k": 128},
            "predicted": {"predicted_ms": predicted_ms, "bound": "hbm",
                          "fits": True, "vmem_bytes": vmem,
                          "bytes_lower_bound": True}}


class TestTuningDB:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add(_rec(config={"fa_block_q": 128, "fa_block_k": 128}))
        db.add(_rec(config={"fa_block_q": 256, "fa_block_k": 256},
                    predicted_ms=8.0))
        db.save()
        db2 = tune_db.TuningDB.open(path)
        assert len(db2.records()) == 2
        assert tune_db.validate(db2.data) == []
        # predicted tier: lower roofline ms ranks first
        assert db2.best(family="flash_attention").config[
            "fa_block_q"] == 256

    def test_add_replaces_same_config(self, tmp_path):
        db = tune_db.TuningDB(str(tmp_path / "db.json"))
        db.add(_rec(predicted_ms=10.0))
        db.add(_rec(predicted_ms=7.0))  # re-sweep, same config key
        assert len(db.records()) == 1
        assert db.best().predicted["predicted_ms"] == 7.0

    def test_vmem_utilization_tiebreak(self, tmp_path):
        # cost_analysis can't see inside the pallas call (§8) so
        # roofline ms ties across block sizes — the fatter in-budget
        # tiling must rank first
        db = tune_db.TuningDB(str(tmp_path / "db.json"))
        db.add(_rec(config={"fa_block_q": 128, "fa_block_k": 128},
                    predicted_ms=10.0, vmem=786432))
        db.add(_rec(config={"fa_block_q": 512, "fa_block_k": 512},
                    predicted_ms=10.0, vmem=3145728))
        assert db.best().config["fa_block_q"] == 512

    def test_predicted_to_measured_upgrade(self, tmp_path):
        path = str(tmp_path / "db.json")
        db = tune_db.TuningDB(path)
        db.add(_rec(config={"fa_block_q": 128, "fa_block_k": 128},
                    predicted_ms=10.0))
        db.add(_rec(config={"fa_block_q": 512, "fa_block_k": 512},
                    predicted_ms=5.0))
        # offline ranking says 512 wins; the chip says 128 does
        loser = db.best()
        assert loser.config["fa_block_q"] == 512
        rec128 = [r for r in db.records()
                  if r.config["fa_block_q"] == 128][0]
        db.upgrade_measured(rec128, 1234.5, unit="img/s/chip")
        db.save()
        db2 = tune_db.TuningDB.open(path)
        best = db2.best(family="flash_attention")
        # measured tier beats every predicted entry
        assert best.config["fa_block_q"] == 128
        assert best.measured["value"] == 1234.5
        assert tune_db.validate(db2.data) == []

    def test_validate_rejects_malformed(self):
        assert tune_db.validate([]) != []
        assert tune_db.validate({"version": 99, "records": []}) != []
        bad = {"version": 1, "records": [{"program": "x"}]}
        assert any("missing" in p for p in tune_db.validate(bad))
        bad_gen = {"version": 1, "records": [_rec(gen="v99")]}
        assert any("generation" in p for p in tune_db.validate(bad_gen))

    def test_fingerprint_mismatch_falls_back(self, tmp_path):
        db = tune_db.TuningDB(str(tmp_path / "db.json"))
        db.add(_rec(fp=tune_db.fingerprint({"program": "p", "v": 1})))
        fp_now = tune_db.fingerprint({"program": "p", "v": 2})
        # the program changed since the sweep: stale tuning must not apply
        assert db.lookup("flash_mha_s2048_d64", fp_now) is None
        fp_same = tune_db.fingerprint({"program": "p", "v": 1})
        assert db.lookup("flash_mha_s2048_d64", fp_same) is not None

    def test_env_overrides_mapping(self):
        rec = tune_db.Record(_rec(config={
            "fa_block_q": 256, "fa_block_k": 512,
            "xla_opts": {"b": "2", "a": "1"}, "batch": 256}))
        assert rec.env_overrides() == {
            "TPUFRAME_FA_BLOCK_Q": "256", "TPUFRAME_FA_BLOCK_K": "512",
            "TPUFRAME_XLA_OPTS": "a=1,b=2",
            "TPUFRAME_BENCH_BATCH": "256"}


class TestResolution:
    """env override > measured > default (flash blocks; the other resolvers
    still take a predicted row) — and no DB effect
    at all when the target generation is unknown (the tier-1 guarantee:
    CPU tests always see the hard defaults)."""

    @pytest.fixture
    def seeded_db(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add(_rec(config={"fa_block_q": 512, "fa_block_k": 256},
                    predicted_ms=5.0))
        db.add(_rec(program="bench_resnet50_b256",
                    family="bench_resnet50",
                    config={"xla_opts": {"xla_opt_x": "1"},
                            "opts_name": "seeded", "batch": 256},
                    predicted_ms=100.0))
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
        monkeypatch.delenv("TPUFRAME_FA_BLOCK_Q", raising=False)
        monkeypatch.delenv("TPUFRAME_FA_BLOCK_K", raising=False)
        monkeypatch.delenv("TPUFRAME_XLA_OPTS", raising=False)
        return db

    @pytest.fixture
    def measured_fa(self, seeded_db):
        """The seeded flash row, run on a chip: only then may it choose."""
        rec = seeded_db.best(family="flash_attention")
        seeded_db.upgrade_measured(rec, 7.5, unit="ms", maximize=False)
        seeded_db.save()

    def test_no_generation_means_defaults(self, seeded_db, measured_fa):
        assert tune_db.resolve_fa_blocks(128, 128) == (128, 128)
        assert tune_db.resolve_xla_opts("bench_resnet50_b256") is None

    def test_db_applies_when_generation_known(self, seeded_db, measured_fa,
                                              monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert tune_db.resolve_fa_blocks(128, 128) == (512, 256)
        assert tune_db.resolve_xla_opts("bench_resnet50_b256") == {
            "xla_opt_x": "1"}

    def test_predicted_fa_row_never_outranks_the_caller(self, seeded_db,
                                                        monkeypatch):
        # a flash row nobody ran on a chip is a hypothesis: the caller's
        # default stands (None in ops/flash_attention.py: its shape rule)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert tune_db.resolve_fa_blocks(None, None) == (None, None)
        assert tune_db.resolve_fa_blocks(128, 128) == (128, 128)

    def test_env_override_beats_db(self, seeded_db, measured_fa,
                                   monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv("TPUFRAME_FA_BLOCK_Q", "1024")
        q, k = tune_db.resolve_fa_blocks(128, 128)
        assert (q, k) == (1024, 256)  # env wins per side; DB fills the rest
        monkeypatch.setenv("TPUFRAME_XLA_OPTS", "xla_opt_y=2")
        assert tune_db.resolve_xla_opts("bench_resnet50_b256") is None

    def test_topology_string_names_the_generation(self, seeded_db,
                                                  measured_fa, monkeypatch):
        # TPUFRAME_TUNE_GEN is the one switch that engages the DB; a
        # topology string ("v5e:2x2") names its generation.
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e:2x2")
        assert tune_db.target_generation() == "v5e"
        assert tune_db.resolve_fa_blocks(128, 128) == (512, 256)

    def test_db_off_switch(self, seeded_db, measured_fa, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv("TPUFRAME_TUNE_DB", "off")
        assert tune_db.resolve_fa_blocks(128, 128) == (128, 128)

    def test_corrupt_db_never_raises(self, tmp_path, monkeypatch):
        path = str(tmp_path / "bad.json")
        with open(path, "w") as f:
            f.write("{not json")
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert tune_db.resolve_fa_blocks(128, 128) == (128, 128)

    def test_weight_update_unknown_family_falls_back(self, tmp_path,
                                                     monkeypatch,
                                                     recwarn):
        # A fresh DB that has never seen a ``weight_update_*`` sweep (or
        # one from an older schema missing the family entirely) must
        # resolve to None — and through zero1.resolve to the replicated
        # default — without a single warning.
        from tpuframe.parallel import zero1

        path = str(tmp_path / "tune_db.json")
        tune_db.TuningDB(path).save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.delenv("TPUFRAME_WEIGHT_UPDATE", raising=False)
        assert tune_db.resolve_weight_update(
            "train_resnet50_b512",
            family="weight_update_resnet50") is None
        assert zero1.resolve(program="train_resnet50_b512",
                             family="weight_update_resnet50") == \
            ("replicated", "default")
        assert len(recwarn) == 0

    def test_weight_update_env_set_means_db_abstains(self, seeded_db,
                                                     monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv("TPUFRAME_WEIGHT_UPDATE", "replicated")
        # env ownership is unambiguous: the DB layer returns None so
        # the caller's env parse is the only authority
        assert tune_db.resolve_weight_update("anything") is None


class TestFingerprint:
    def test_opts_change_fingerprint(self):
        desc = {"program": "bench_resnet50_b256", "n_chips": 4}
        base = tune_db.fingerprint(desc, {})
        seeded = tune_db.fingerprint(
            desc, {"xla_tpu_enable_latency_hiding_scheduler": "true"})
        assert base != seeded
        # order-insensitive within a set
        assert tune_db.fingerprint(desc, {"a": "1", "b": "2"}) == \
            tune_db.fingerprint(desc, {"b": "2", "a": "1"})

    def test_lowered_text_based_fingerprint_cpu(self):
        # the sweep fingerprints (program desc, opts); a seeded option
        # set must change the fingerprint even when the lowered module
        # text is identical — verified against a real CPU lowering
        import hashlib

        import jax
        import jax.numpy as jnp

        lowered = jax.jit(lambda x: x * 2 + 1).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32))
        desc = {"hlo_sha": hashlib.sha256(
            lowered.as_text().encode()).hexdigest()}
        a = tune_db.fingerprint(desc)
        b = tune_db.fingerprint(
            desc, {"xla_tpu_enable_latency_hiding_scheduler": "true"})
        assert a != b
        assert tune_db.fingerprint(desc) == a  # deterministic


class TestXlaOptsHelper:
    def test_parse(self):
        from tpuframe.utils import xla_opts

        assert xla_opts.parse("a=1, b=2 ,") == {"a": "1", "b": "2"}
        with pytest.raises(ValueError):
            xla_opts.parse("a=1,noequals")
        with pytest.raises(ValueError):
            xla_opts.parse("=v")
        assert xla_opts.format_opts({"b": "2", "a": "1"}) == "a=1,b=2"

    def test_from_env(self, monkeypatch):
        from tpuframe.utils import xla_opts

        monkeypatch.delenv("TPUFRAME_XLA_OPTS", raising=False)
        assert xla_opts.from_env() is None
        monkeypatch.setenv("TPUFRAME_XLA_OPTS", "  ")
        assert xla_opts.from_env() is None
        monkeypatch.setenv("TPUFRAME_XLA_OPTS", "k=v")
        assert xla_opts.from_env() == {"k": "v"}

    def test_candidate_sets_cover_the_levers(self):
        sets = dict(xla_opts_candidate_sets())
        assert sets["baseline"] == {}
        assert "xla_tpu_enable_latency_hiding_scheduler" in \
            sets["latency_hiding"]
        assert "xla_tpu_scoped_vmem_limit_kib" in sets["scoped_vmem_64m"]
        # combiner set derives from parallel/tuning.py's flag template
        assert sets["combine_64m"] == {
            "xla_gpu_all_reduce_combine_threshold_bytes": "67108864"}


class TestReplayAdapter:
    def test_offline_topk_upgrade(self, tmp_path):
        from tpuframe.obs import autotune

        path = str(tmp_path / "db.json")
        db = tune_db.TuningDB(path)
        db.add(_rec(config={"fa_block_q": 128, "fa_block_k": 128},
                    predicted_ms=10.0))
        db.add(_rec(config={"fa_block_q": 256, "fa_block_k": 256},
                    predicted_ms=8.0))
        db.add(_rec(config={"fa_block_q": 512, "fa_block_k": 512},
                    predicted_ms=6.0))
        seen = []

        def measure(env):
            seen.append(env)
            # the chip disagrees with the roofline ranking: 128 wins
            return 1000.0 / int(env["TPUFRAME_FA_BLOCK_Q"])

        report = autotune.replay_offline_topk(
            measure, family="flash_attention", generation="v5e", k=2,
            db=db)
        # top-2 by predicted ms: 512 then 256 — both replayed via env
        assert [e["TPUFRAME_FA_BLOCK_Q"] for e in seen] == ["512", "256"]
        assert report.best_env["TPUFRAME_FA_BLOCK_Q"] == "256"
        db2 = tune_db.TuningDB.open(path)  # saved by the adapter
        measured = [r for r in db2.records() if r.measured]
        assert len(measured) == 2  # losers are upgraded too
        assert db2.best().config["fa_block_q"] == 256

    def test_failed_trial_keeps_predicted(self, tmp_path):
        from tpuframe.obs import autotune

        db = tune_db.TuningDB(str(tmp_path / "db.json"))
        db.add(_rec(predicted_ms=10.0))

        def measure(env):
            raise RuntimeError("chip down")

        report = autotune.replay_offline_topk(
            measure, family="flash_attention", db=db, save=False)
        assert report.trials[0]["value"] is None
        assert "chip down" in report.trials[0]["error"]
        assert db.records()[0].measured is None


class TestCompileCache:
    def test_second_compile_records_hit(self, tmp_path, monkeypatch):
        """The acceptance-criteria path: a second compile of the same
        program is served by the persistent cache and shows up in the
        obs.metrics counters — the warm restart PR 2's relaunch loop
        gets for free."""
        import jax
        import jax.numpy as jnp

        from tpuframe.obs import metrics as obs_metrics
        from tpuframe.utils import compile_cache

        old_dir = jax.config.jax_compilation_cache_dir
        old_min_s = jax.config.jax_persistent_cache_min_compile_time_secs
        old_min_b = jax.config.jax_persistent_cache_min_entry_size_bytes
        obs_metrics.reset_counters("compile_cache.")
        try:
            # The standard variable places the cache; jax read it at
            # import, so the test stands in for that.
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / "cache"))
            jax.config.update("jax_compilation_cache_dir",
                              str(tmp_path / "cache"))
            got = compile_cache.enable(min_compile_secs=0.0,
                                       min_entry_size_bytes=-1)
            assert got == str(tmp_path / "cache")

            def f(x):
                return jnp.sin(x) * jnp.cos(x) + x @ x.T

            x = jnp.ones((64, 64), jnp.float32)
            jax.jit(f)(x)  # cold: compiles, writes the cache
            c = obs_metrics.counters("compile_cache.")
            assert c.get("compile_cache.misses", 0) >= 1
            # clear the in-memory caches to simulate a relaunched
            # process, then recompile the same program: it must be
            # served by the persistent cache on disk
            jax.clear_caches()
            jax.jit(f)(x)
            c = obs_metrics.counters("compile_cache.")
            assert c.get("compile_cache.hits", 0) >= 1
        finally:
            jax.config.update("jax_compilation_cache_dir", old_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", old_min_s)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", old_min_b)
            obs_metrics.reset_counters("compile_cache.")

    def test_off_switch(self, monkeypatch):
        from tpuframe.utils import compile_cache

        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "off")
        assert compile_cache.enable() is None

    def test_default_dir_is_repo_xla_cache(self):
        from tpuframe.utils import compile_cache

        assert compile_cache.default_cache_dir().endswith(".xla_cache")


class TestTuneCheck:
    def test_self_check_clean(self):
        import tpuframe.tune as tune

        assert tune.check() == []

    def test_self_check_flags_bad_db(self, tmp_path):
        import tpuframe.tune as tune

        bad = tmp_path / "db.json"
        bad.write_text(json.dumps({"version": 1,
                                   "records": [{"program": "x"}]}))
        problems = tune.check(db_path=str(bad))
        assert any("missing" in p for p in problems)


class TestShippedDB:
    def test_shipped_db_validates(self):
        """The committed tune_db.json (written by the sweep) must always
        pass schema validation — same check the analysis gate runs."""
        path = os.path.join(tune_db.repo_root(), "tune_db.json")
        if not os.path.exists(path):
            pytest.skip("no shipped tuning DB")
        with open(path) as f:
            data = json.load(f)
        assert tune_db.validate(data) == []
        db = tune_db.TuningDB(path, data)
        # a flash row is there because a chip ran it: the nine predicted
        # ones went when the kernel's shape rule came (PR 26; ROADMAP D1)
        assert all(r.measured for r in db.records(family="flash_attention"))
        # acceptance floor: >=2 opts sets
        bench = db.records(family="bench_resnet50")
        assert len({r.config.get("opts_name") for r in bench}) >= 2


# ---------------------------------------------------------------------------
# tune plan: the static auto-parallelism planner
# ---------------------------------------------------------------------------

from tpuframe.tune import plan  # noqa: E402


def _plan_row(name, spec, total, comm, **over):
    r = {"name": name, "spec": spec, "slices": 1, "n_devices": 4,
         "compile_topology": "v5e:2x2", "config": {}, "status": "ok",
         "detector_problems": [], "budget_findings": [],
         "predicted_step_ms": round(total - 0.001, 6), "t_ici_ms": 0.001,
         "t_dcn_ms": 0.0, "ici_bytes": comm, "dcn_bytes": 0,
         "comm_bytes": comm, "predicted_total_ms": total,
         "overlap_potential": 0.5, "bound": "hbm", "fits": True,
         "peak_memory_bytes": 1 << 20}
    r.update(over)
    return r


def _plan_report():
    """A synthetic report exercising ranking, admissibility and all three
    pinned verdicts — shaped exactly like a real `tune plan` emission."""
    rows = [
        _plan_row("spec:dp=*", "dp=*", 0.03, 300),
        _plan_row("spec:dp=*+zero1", "dp=*", 0.04, 600),
        _plan_row("spec:dp=*;slices=2", "dp=*;slices=2", 0.09, 400,
                  slices=2, n_devices=8, t_dcn_ms=0.05, dcn_bytes=400),
        _plan_row("spec:dp=*;slices=2+hier", "dp=*;slices=2", 0.07, 500,
                  slices=2, n_devices=8, t_dcn_ms=0.0125, ici_bytes=400,
                  dcn_bytes=100),
        _plan_row("spec:dp=2,fsdp=2;slices=2", "dp=2,fsdp=2;slices=2",
                  0.06, 1000, slices=2, n_devices=8, t_ici_ms=0.004,
                  t_dcn_ms=0.025, ici_bytes=800, dcn_bytes=200),
        _plan_row("spec:dp=*,tp=2", "dp=*,tp=2", 0.01, 10,
                  status="inadmissible",
                  detector_problems=["seeded structural finding"]),
    ]
    ranking = plan.rank_rows(rows)
    return {"schema": plan.PLAN_SCHEMA, "jax": plan._jax_version(),
            "topology": "v5e:2x2", "generation": "v5e",
            "objective": "step + wire", "slice_counts": [1, 2],
            "candidates": rows, "skips": [], "ranking": ranking,
            "winner": rows[0], "verdicts": plan.compute_verdicts(rows)}


class TestPlanner:
    def test_scaled_topology(self):
        assert plan._scaled_topology("v5e:2x2", 1) == "v5e:2x2"
        assert plan._scaled_topology("v5e:2x2", 2) == "v5e:2x4"
        assert plan._scaled_topology("v4:2x2x2", 4) == "v4:2x2x8"

    def test_enumerate_candidates_includes_fused_variants(self):
        """The planner carries the bucketed-fusion modifiers (dp and
        dp+zero1) at the registry threshold, on every slice count — so
        overlap potential participates in predicted_total_ms ranking."""
        for n_slices in (1, 2):
            cands = plan.enumerate_candidates(8, n_slices)
            fused = [c for c in cands if "fusion_threshold" in c]
            assert len(fused) == 2
            assert all(c["fusion_threshold"] == 131072 for c in fused)
            assert {c.get("weight_update") for c in fused} == \
                {None, "zero1"}

    def test_rank_rows_excludes_inadmissible_and_is_total(self):
        rows = _plan_report()["candidates"]
        ranking = plan.rank_rows(rows)
        assert ranking[0] == "spec:dp=*"          # lowest admissible total
        assert "spec:dp=*,tp=2" not in ranking    # 0.01 ms but flagged
        assert ranking == plan.rank_rows(list(reversed(rows)))

    def test_verdicts_hold_on_synthetic_rows(self):
        v = plan.compute_verdicts(_plan_report()["candidates"])
        assert v["zero1_bytes"]["holds"] is True       # 300 < 600
        assert v["hier_dcn"]["holds"] is True          # 100/400 <= 1/2
        assert v["dcn_split"]["holds"] is True         # 0.025>0.004, 200<800
        # missing rows degrade to holds=None, never a crash
        assert plan.compute_verdicts([])["zero1_bytes"]["holds"] is None

    def test_check_clean_then_catches_tampering(self, tmp_path):
        import copy as copy_lib

        path = str(tmp_path / "plan_report.json")
        report = _plan_report()
        with open(path, "w") as f:
            json.dump(report, f)
        assert plan.check(path) == []

        tampered = copy_lib.deepcopy(report)
        tampered["ranking"] = list(reversed(tampered["ranking"]))
        with open(path, "w") as f:
            json.dump(tampered, f)
        assert any("ranking drift" in p for p in plan.check(path))

        tampered = copy_lib.deepcopy(report)
        tampered["verdicts"]["zero1_bytes"]["holds"] = False
        with open(path, "w") as f:
            json.dump(tampered, f)
        assert any("disagree" in p for p in plan.check(path))

    def test_check_flags_verdict_that_stopped_holding(self, tmp_path):
        """A verdict that re-derives to holds=False is a FINDING — the
        rows contradict the pinned PERF direction."""
        report = _plan_report()
        for r in report["candidates"]:
            if r["name"] == "spec:dp=*+zero1":
                r["comm_bytes"] = 100      # now dp moves MORE bytes
        report["verdicts"] = plan.compute_verdicts(report["candidates"])
        report["ranking"] = plan.rank_rows(report["candidates"])
        report["winner"] = next(r for r in report["candidates"]
                                if r["name"] == report["ranking"][0])
        path = str(tmp_path / "plan_report.json")
        with open(path, "w") as f:
            json.dump(report, f)
        assert any("does NOT hold" in p for p in plan.check(path))

    def test_seeded_ranking_positive(self):
        report = _plan_report()
        assert plan._seeded_ranking_positive(report) == []
        thin = dict(report, candidates=report["candidates"][:1],
                    ranking=report["ranking"][:1])
        assert any("cross-checked" in p
                   for p in plan._seeded_ranking_positive(thin))

    def test_version_skew_skips(self, tmp_path):
        report = _plan_report()
        report["jax"] = "0.0.0-some-other-jax"
        path = str(tmp_path / "plan_report.json")
        with open(path, "w") as f:
            json.dump(report, f)
        assert plan.check(path) == []

    def test_missing_report_is_a_finding(self, tmp_path):
        problems = plan.check(str(tmp_path / "nope.json"))
        assert any("tune plan" in p for p in problems)

    def test_shipped_report_passes_check(self):
        """The committed plan report must stay re-derivable — the same
        leg the analysis gate runs."""
        path = plan.default_report_path()
        if not os.path.exists(path):
            pytest.skip("no shipped plan report")
        assert plan.check(path) == []


class TestResolveSpec:
    """db.resolve_spec: env > DB > default, generation-gated like every
    other tuned knob — CPU tier-1 runs must never see a planned spec."""

    @pytest.fixture
    def seeded(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune_db.json")
        db = tune_db.TuningDB(path)
        db.add({"program": "train_lm_tiny", "family": "plan_spec",
                "fingerprint": "f" * 32, "topology": "v5e:2x2",
                "generation": "v5e", "config": {"spec": "dp=*,ep=2"},
                "predicted": {"predicted_ms": 0.03, "source": "planned"}})
        db.save()
        monkeypatch.setenv("TPUFRAME_TUNE_DB", path)
        for var in ("TPUFRAME_SPEC", "TPUFRAME_TUNE_GEN"):
            monkeypatch.delenv(var, raising=False)

    def test_no_generation_no_resolution(self, seeded):
        assert tune_db.resolve_spec("train_lm_tiny") is None

    def test_generation_gated_resolution(self, seeded, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        assert tune_db.resolve_spec("train_lm_tiny") == "dp=*,ep=2"
        # unknown program falls back to the family winner
        assert tune_db.resolve_spec("other_prog") == "dp=*,ep=2"

    def test_env_spec_abstains(self, seeded, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
        monkeypatch.setenv("TPUFRAME_SPEC", "dp=4")
        assert tune_db.resolve_spec("train_lm_tiny") is None

    def test_env_overrides_carries_spec(self, seeded):
        db = tune_db.TuningDB.open()
        rec = db.best(family="plan_spec")
        assert rec.env_overrides()["TPUFRAME_SPEC"] == "dp=*,ep=2"
