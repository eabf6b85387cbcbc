"""flops.py against bench.py's count for ResNet-50 and a hand count for
the LM."""

import json
import os

import pytest

from conftest import BENCH, ROOT


def _arch(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["arch"]


def test_resnet50_against_bench_py(bench):
    flops = bench.load_module(os.path.join(BENCH, "flops.py"))
    got = flops.resnet_train_flops_per_image(_arch("resnet50"))
    # bench.py: 1.252e13 / 512 from the compiled step's cost_analysis,
    # which also counts the elementwise work; shapes alone give a little
    # less.
    assert got == pytest.approx(1.252e13 / 512, rel=0.02)
    assert got < 1.252e13 / 512
    assert sum(1 for layer in flops.resnet_conv_layers(_arch("resnet50"))
               ) == 54  # 53 convolutions and the classifier


def test_lm_hand_count(bench):
    flops = bench.load_module(os.path.join(BENCH, "flops.py"))
    arch = _arch("lm124m")
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    assert flops.lm_matmul_params(arch) == n == 123_532_032
    attn_fwd = 12 * 2 * (2 * 2048 * 2048 * 768) / 2
    want = 6 * n * 2048 + 3 * attn_fwd
    assert flops.lm_train_flops_per_sequence(arch, 2048) == pytest.approx(want)
    assert want == pytest.approx(1.750e12, rel=1e-3)
    assert flops.lm_serve_flops(arch, 100, 28) == 2 * n * 128


def test_flash_work_and_peaks(bench):
    flops = bench.load_module(os.path.join(BENCH, "flops.py"))
    w = flops.flash_attention_work(8, 2048, 12, 64)
    one = 2 * 8 * 12 * 2048 * 2048 * 64 / 2
    assert w["fwd"]["flops"] == 2 * one and w["bwd"]["flops"] == 5 * one
    assert w["fwd"]["bytes"] == 4 * 8 * 2048 * 12 * 64 * 2
    peaks = flops.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    secs, bound = flops.roofline_seconds(w["bwd"], peaks)
    assert bound == "compute" and secs == pytest.approx(5 * one / 197e12)
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")
    assert os.path.isfile(os.path.join(ROOT, "bench.py"))
