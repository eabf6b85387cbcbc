"""End-to-end harness tests: each workload config's graph runs a few steps on
the fake cluster; smoke config converges; checkpoint resume continues exactly."""

import jax
import numpy as np
import pytest

from tpuframe import train as train_mod
from tpuframe.utils import get_config
from tpuframe.utils.config import WORKLOADS


class TestConfigs:
    def test_all_workloads_defined(self):
        # the five reference configs [B:6-12] + smoke
        assert {"mnist_single", "cifar10_resnet18", "imagenet_resnet50",
                "glue_bert", "imagenet_resnet50_pod"} <= set(WORKLOADS)

    def test_overrides(self):
        cfg = get_config("smoke").with_overrides(total_steps=5)
        assert cfg.total_steps == 5
        with pytest.raises(ValueError):
            cfg.with_overrides(nonsense=1)

    def test_kwargs_overrides_merge_not_replace(self):
        # `--set model_kwargs={"moe_experts": 4}` on a tiny config must keep
        # the config's own kwargs (dropping them silently rebuilds the model
        # at full default size — a 219M-param lm_smoke).
        cfg = get_config("lm_smoke").with_overrides(
            model_kwargs={"moe_experts": 4})
        assert cfg.model_kwargs["moe_experts"] == 4
        assert cfg.model_kwargs["tiny"] is True  # preserved
        assert cfg.dataset_kwargs["seq_len"] == 64  # untouched field
        # per-key override still wins
        cfg2 = cfg.with_overrides(model_kwargs={"tiny": False})
        assert cfg2.model_kwargs["tiny"] is False
        assert cfg2.model_kwargs["moe_experts"] == 4
        # None deletes a key — the replace escape hatch
        cfg3 = cfg2.with_overrides(model_kwargs={"seq_mode": None})
        assert "seq_mode" not in cfg3.model_kwargs


class TestEndToEnd:
    def test_smoke_converges_single_process(self, tmp_path):
        cfg = get_config("smoke").with_overrides(
            distributed=False, total_steps=60, log_every=20, eval_every=30)
        metrics = train_mod.train(cfg)
        assert metrics["step"] == 60
        assert metrics["loss"] < 1.0  # synthetic MNIST is very learnable
        assert "eval_accuracy" in metrics

    def test_smoke_distributed_matches_single(self):
        """Golden invariant at harness level: same config, same seeds —
        distributed (8-chip) and single-process loss match closely."""
        cfg1 = get_config("smoke").with_overrides(distributed=False,
                                                  total_steps=20, log_every=20)
        cfg8 = get_config("smoke").with_overrides(total_steps=20, log_every=20)
        m1 = train_mod.train(cfg1)
        m8 = train_mod.train(cfg8)
        # dropout rngs differ (per-replica decorrelation), so allow slack
        assert abs(m1["loss"] - m8["loss"]) < 0.35, (m1["loss"], m8["loss"])

    @pytest.mark.parametrize("ckpt_async", [False, True])
    def test_resume_continues_exactly(self, tmp_path, ckpt_async):
        """Resume == straight run, for sync and async checkpointing (the
        async case proves the background write/restore round-trip, not
        mid-run commit timing — train() drains pending saves on exit)."""
        ck = str(tmp_path / "ck")
        base = get_config("smoke").with_overrides(
            ckpt_dir=ck, ckpt_every=10, total_steps=20, log_every=10,
            ckpt_async=ckpt_async)
        # run 20 steps straight through
        straight = train_mod.train(base)
        # run 10, stop, then "restart the job" and run to 20
        train_mod.train(base.with_overrides(total_steps=10,
                                            ckpt_dir=ck + "2"))
        part2 = train_mod.train(base.with_overrides(ckpt_dir=ck + "2"))
        assert part2["step"] == 20
        np.testing.assert_allclose(straight["loss"], part2["loss"],
                                   rtol=1e-4)

    def test_smoke_track_best_saves_best_eval(self, tmp_path):
        """track_best: a best/ checkpoint exists after training and holds
        the step with the lowest eval loss seen."""
        import json

        ck = tmp_path / "ck"
        cfg = get_config("smoke").with_overrides(
            distributed=False, total_steps=30, log_every=10, eval_every=10,
            ckpt_dir=str(ck), ckpt_every=100, track_best=True)
        train_mod.train(cfg)
        record = json.loads((ck / "best" / "metric.json").read_text())
        assert record["mode"] == "min" and record["step"] in (10, 20, 30)
        best_dirs = [p.name for p in (ck / "best").iterdir() if p.is_dir()]
        assert len(best_dirs) == 1

    def test_smoke_lars_optimizer_learns(self):
        """LARS (the large-batch ImageNet scaling recipe): layerwise
        trust-ratio optimizer runs through the harness and decreases
        loss; BN/bias leaves excluded from decay+adaptation."""
        cfg = get_config("smoke").with_overrides(
            distributed=False, optimizer="lars", base_lr=1.0,
            weight_decay=1e-4, total_steps=40, log_every=20, eval_every=100)
        metrics = train_mod.train(cfg)
        assert metrics["step"] == 40
        assert np.isfinite(metrics["loss"]) and metrics["loss"] < 2.0

    def test_bf16_harness_keeps_the_rounded_column_only(self, monkeypatch):
        """``build_harness`` under bf16 compute: each loader holds its
        image column in bfloat16, ``model.init`` still saw float32 rows,
        and once the harness is built nothing keeps the float32 columns
        alive (the loaders cast their own copies once)."""
        import gc
        import weakref

        import jax.numpy as jnp

        refs = []
        build = train_mod.build_datasets

        def watched(cfg):
            made = build(cfg)
            for ds in made:
                assert ds.columns["image"].dtype == np.float32
                refs.append(weakref.ref(ds.columns["image"]))
            return made

        monkeypatch.setattr(train_mod, "build_datasets", watched)
        h = train_mod.build_harness(get_config("smoke").with_overrides(
            distributed=False, compute_dtype="bfloat16"))
        try:
            for loader in (h.train_loader, h.eval_loader):
                assert loader.dataset.columns["image"].dtype == jnp.bfloat16
                assert loader.dataset.columns["label"].dtype == np.int32
            gc.collect()
            assert len(refs) == 2 and all(r() is None for r in refs)
            assert next(iter(h.train_loader))["image"].dtype == jnp.bfloat16
        finally:
            h.train_loader.close()
            h.eval_loader.close()

    def test_cifar_resnet18_steps(self):
        cfg = get_config("cifar10_resnet18").with_overrides(
            total_steps=3, global_batch=16, warmup_steps=1, log_every=1,
            eval_every=3, eval_batches=1,
            dataset_kwargs={"synthetic_size": 64})
        metrics = train_mod.train(cfg)
        assert metrics["step"] == 3
        assert np.isfinite(metrics["loss"])

    def test_glue_bert_tiny_steps(self):
        """BERT path end-to-end — same graph as config 4, tiny dimensions
        (model_kwargs flow straight into BertConfig)."""
        cfg = get_config("glue_bert").with_overrides(
            total_steps=2, global_batch=8, warmup_steps=1, log_every=1,
            eval_every=2, eval_batches=1,
            dataset_kwargs={"synthetic_size": 32, "seq_len": 32,
                            "vocab_size": 512},
            model_kwargs={"vocab_size": 512, "hidden_size": 64,
                          "num_layers": 2, "num_heads": 2,
                          "intermediate_size": 128, "max_position": 32})
        metrics = train_mod.train(cfg)
        assert metrics["step"] == 2
        assert np.isfinite(metrics["loss"])

    def test_mnist_single_config_runs(self):
        cfg = get_config("mnist_single").with_overrides(
            total_steps=4, log_every=2, eval_every=4, eval_batches=1,
            dataset_kwargs={"synthetic_size": 256})
        metrics = train_mod.train(cfg)
        assert metrics["step"] == 4

    def test_cli_main(self, capsys):
        metrics = train_mod.main([
            "--config", "smoke", "--set", "total_steps=4",
            "--set", "log_every=2", "--set", "eval_every=4",
            "--set", "eval_batches=1"])
        assert metrics["step"] == 4
        out = capsys.readouterr().out
        assert "[tpuframe] done" in out
        # The run's own threads are stopped and joined before it returns
        # (a prefetch worker caught inside device_put when the interpreter
        # exits aborts the process after "done").
        import threading
        left = [t.name for t in threading.enumerate()
                if t.name.startswith("tpuframe-")]
        assert left == [], left
