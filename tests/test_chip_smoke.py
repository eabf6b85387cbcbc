"""``chip_smoke.py`` and ``bench.py`` off the chip: both refuse a CPU, and
the smoke's phases rehearse at toy size (``-m slow``) before a chip call
is spent on them (on-chip-measurement guide §2)."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_refuses_a_cpu_and_prints_no_result(script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(REPO / script)], env=env,
                          cwd=str(REPO), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"value"' not in proc.stdout      # bench.py's result key
    assert "TPU" in proc.stderr


def test_all_reduce_group_sizes_reads_both_spellings(smoke):
    hlo = "\n".join([
        "  %ar.1 = f32[768]{0} all-reduce(f32[768]{0} %p), channel_id=1, "
        "replica_groups={{0,1,2,3}}, use_global_device_ids=true",
        "  %ar.2 = (f32[8]{0}, f32[4]{0}) all-reduce-start(%a, %b), "
        "replica_groups=[1,4]<=[4], to_apply=%add",
        "  %ar.3 = f32[8]{0} all-reduce(%c), replica_groups={{0,1},{2,3}}",
        "  %ag = f32[8]{0} all-gather(%d), replica_groups={{0,1,2,3}}",
    ])
    assert smoke.all_reduce_group_sizes(hlo) == [4, 4, 2]


def test_a_repeated_set_of_a_dict_field_merges(smoke):
    """The smoke's XLA-attention run is LM_SETS plus one more
    ``--set model_kwargs=``: the second adds to the first."""
    from tpuframe import train
    from tpuframe.utils import get_config

    kv = train._parse_set([*smoke.LM_SETS,
                           'model_kwargs={"attn_impl": "xla"}',
                           "total_steps=2"])
    assert kv["model_kwargs"] == {"seq_mode": None, "max_seq": 2048,
                                  "attn_impl": "xla"}
    assert kv["total_steps"] == 2            # a scalar still replaces
    cfg = get_config("lm_long").with_overrides(**kv)
    assert cfg.model_kwargs["attn_impl"] == "xla"
    assert cfg.model_kwargs["max_seq"] == 2048
    assert "seq_mode" not in cfg.model_kwargs  # None deletes lm_long's ring


# --- rehearsals: every phase at toy size, on the CPU ------------------------

TOY_LM = (
    "global_batch=8", "total_steps=3", "log_every=1", "eval_every=3",
    "eval_batches=1", "warmup_steps=0", "shard_seq=False",
    'mesh={"data": -1}', "compute_dtype=float32",
    'model_kwargs={"seq_mode": None, "max_seq": 256, "tiny": True, '
    '"vocab_size": 512}',
    'dataset_kwargs={"seq_len": 256, "vocab_size": 512, '
    '"synthetic_size": 64}',
)


@pytest.fixture
def rehearsal(monkeypatch, tmp_path, smoke):
    """The smoke fails on an assumed generation, so the rehearsal names
    one (without the tuning DB's rows) and writes under tmp_path."""
    monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
    monkeypatch.setenv("TPUFRAME_TUNE_DB", "off")
    monkeypatch.setattr(smoke, "OUT_DIR", str(tmp_path))
    yield smoke
    from tpuframe.obs import events

    events.close()


@pytest.mark.slow
def test_rehearse_device_and_trainer_phases(rehearsal):
    n = len(jax.devices())
    assert rehearsal.phase_device(require_tpu=False, chips=n)["count"] == n
    out = rehearsal.phase_resnet(
        config="smoke",
        sets=("log_every=1", "eval_every=4", "eval_batches=1",
              "ckpt_every=2"), total=4, resume_steps=6)
    assert out["resumed_from"] == 4 and out["resume_steps"] == [5, 6]
    out = rehearsal.phase_lm(sets=TOY_LM, flash_shape=(2, 256, 4, 64),
                             want_impl="interpret")
    assert out["abs_loss_diff_vs_xla"][0] <= rehearsal.LOSS_TOL_ATTN


@pytest.mark.slow
def test_rehearse_server_phase(rehearsal, monkeypatch):
    monkeypatch.setenv("TPUFRAME_SERVE_BUCKETS", "16,32")
    monkeypatch.setenv("TPUFRAME_DECODE_BLOCK", "16")
    out = rehearsal.phase_server(
        serve_args=("--model", "tiny-lm", "--requests", "6", "--steps",
                    "2000", "--slots", "2", "--max-new-tokens", "4"),
        parity_buckets=(16, 32), parity_atol=2e-5)
    assert out["finished"] == 6 and out["prompt_buckets_hit"] == [16, 32]


@pytest.mark.slow
def test_rehearse_data_parallel_phase(rehearsal):
    n = len(jax.devices())          # the 8 virtual devices of conftest.py
    out = rehearsal.phase_dp(sets=TOY_LM, n=n, want_impl="interpret")
    assert out["all_reduce_group_sizes"] == [n]
    assert max(out["abs_loss_diff"]) <= rehearsal.LOSS_TOL_DP
