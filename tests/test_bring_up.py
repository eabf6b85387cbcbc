"""What a run may assume about the device, the compile cache and its own
threads — the facts ``chip_smoke.py`` holds a chip run to (PR 21)."""

import threading
import types

import jax
import numpy as np
import pytest

from tpuframe.tune import roofline
from tpuframe.utils import compile_cache


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


class TestDeviceGeneration:
    @pytest.fixture(autouse=True)
    def no_override(self, monkeypatch):
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)

    @pytest.mark.parametrize("kind,gen", [
        ("TPU v5 lite", "v5e"), ("TPU v4", "v4"), ("TPU v6 lite", "v6e"),
        ("TPU v5p", "v5p"),
    ])
    def test_device_kind_names_a_row_of_the_peak_table(self, kind, gen):
        assert roofline.device_generation(_device("tpu", kind)) == \
            (gen, "device")
        assert roofline.get_hardware(gen).bf16_flops > 0

    def test_unknown_tpu_kind_is_an_error_not_a_default(self):
        with pytest.raises(KeyError, match="TPU v9 mega"):
            roofline.device_generation(_device("tpu", "TPU v9 mega"))

    def test_cpu_is_labelled_assumed(self):
        assert roofline.device_generation() == \
            (roofline.ASSUMED_GENERATION, "assumed")

    def test_env_is_the_explicit_override(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v4:2x2x1")
        assert roofline.device_generation(_device("tpu", "TPU v5 lite")) \
            == ("v4", "env")

    def test_detection_does_not_engage_the_tuning_db(self):
        """Only TPUFRAME_TUNE_GEN switches tune_db.json on: a chip that
        names itself must not adopt the 40 predicted rows."""
        from tpuframe.tune import db as tune_db

        assert roofline.device_generation(
            _device("tpu", "TPU v5 lite"))[0] == "v5e"
        assert tune_db.target_generation() is None


class TestCompileCachePlacement:
    @pytest.fixture
    def cache_config(self):
        """Restore jax's cache settings whatever enable() did to them."""
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")
        was = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in was.items():
            jax.config.update(n, v)
        compile_cache.reset_cache()

    def test_standard_variable_is_honoured_and_nothing_set_in_code(
            self, monkeypatch, cache_config, tmp_path):
        monkeypatch.delenv("TPUFRAME_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        # jax read the variable at import; stand in for that here.
        jax.config.update("jax_compilation_cache_dir", "/set/by/jax")
        assert compile_cache.location() == (str(tmp_path), "env")
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "/set/by/jax"

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch,
                                                   cache_config):
        monkeypatch.delenv("TPUFRAME_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = compile_cache.default_cache_dir()
        assert want.endswith("/.xla_cache")
        assert compile_cache.location() == (want, "default")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_private_knob_only_switches_off(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "/some/dir")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.location()[1] == "default"  # not a dir any more
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "off")
        assert compile_cache.location() == (None, "off")


class TestThreadTeardown:
    def _loader(self):
        from tpuframe.data import ShardedLoader
        from tpuframe.data.datasets import ArrayDataset

        ds = ArrayDataset({"x": np.arange(64, dtype=np.float32)[:, None]})
        return ShardedLoader(ds, 8, None, prefetch=2)

    @staticmethod
    def _prefetchers():
        return [t for t in threading.enumerate()
                if t.name == "tpuframe-prefetch"]

    def test_close_joins_the_worker_of_an_unfinished_stream(self):
        loader = self._loader()
        stream = iter(loader)          # the training loop's infinite stream
        next(stream)
        assert len(self._prefetchers()) == 1
        loader.close()
        assert self._prefetchers() == []

    def test_abandoned_epoch_joins_its_worker(self):
        loader = self._loader()
        epoch = loader.epoch(0)
        next(epoch)
        epoch.close()                  # what `break` + gc does in evaluate()
        assert self._prefetchers() == []
        loader.close()                 # idempotent, nothing left to join

    def test_heartbeat_stop_joins_its_thread(self):
        from tpuframe.obs import Heartbeat

        hb = Heartbeat(timeout_s=60, poll_s=30).start()
        hb.stop()
        assert not hb._thread.is_alive()


class TestKernelImplReport:
    @pytest.fixture(autouse=True)
    def fresh(self):
        from tpuframe.ops import kernel_impl

        kernel_impl.reset()
        yield kernel_impl
        kernel_impl.reset()

    def test_logged_once_as_a_run_event(self, fresh, tmp_path):
        from tpuframe.obs import events

        events.init(str(tmp_path))
        try:
            for _ in range(3):
                fresh.record("flash_attention", "interpret", "backend=cpu")
        finally:
            events.close()
        recs = [r for r in events.merge(str(tmp_path))
                if r["type"] == "kernel_impl"]
        assert len(recs) == 1 and events.validate_record(recs[0]) == []
        assert (recs[0]["op"], recs[0]["impl"], recs[0]["why"]) == (
            "flash_attention", "interpret", "backend=cpu")

    def test_kernel_and_its_fallback_both_say_so(self, fresh, capsys):
        import jax.numpy as jnp

        from tpuframe.ops import attention as attn_ops

        ok = jnp.zeros((1, 128, 2, 64), jnp.float32)
        attn_ops.multihead_attention(ok, ok, ok, causal=True, impl="pallas")
        (line,) = capsys.readouterr().out.splitlines()
        assert line.startswith(   # then the tiling and the grid it chose
            "[tpuframe] kernel flash_attention -> interpret (backend=cpu; "
            "fwd q128 k128 sub128 grid 2x1x1; ")
        odd = jnp.zeros((1, 100, 2, 64), jnp.float32)  # 100 does not tile
        attn_ops.multihead_attention(odd, odd, odd, impl="pallas")
        (line,) = capsys.readouterr().out.splitlines()
        assert "flash_attention -> xla" in line and "do not tile" in line

    def test_interpret_override_is_named(self, monkeypatch, fresh):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "0")
        assert fresh.interpret_default() == (
            False, "TPUFRAME_PALLAS_INTERPRET=0")


def test_serve_cli_names_the_124m_config():
    from tpuframe.models.transformer_lm import LMConfig
    from tpuframe.serve import __main__ as serve_cli

    cfg = serve_cli.model_config("lm-124m")
    assert cfg == LMConfig(dtype="bfloat16")
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.vocab_size) == (768, 12, 12, 32000)
    assert serve_cli.parse_args(["--model", "lm-124m", "--requests",
                                 "12"]).requests == 12
    with pytest.raises(SystemExit, match="lm-124m"):
        serve_cli.model_config("gpt-5")
