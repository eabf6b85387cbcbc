"""``correct`` has to come out false when the timed path is broken
underneath, and when the reference is put in the program's place in the
next precision down (the control).  Toy sizes, on the CPU; the chip-size
readings that the cells' limits were set from are in PERF.md."""

import dataclasses
import json
import os

import numpy as np
import pytest

from conftest import BENCH, TOY, toy_args


def _run(bench, workload, **kw):
    return bench.run_cell(toy_args(workload, **kw), require_chip=False,
                          manifest_path=TOY)


def _failed(result) -> list[str]:
    return [k for k, v in result["compared"].items()
            if not v["value"] <= v["limit"]]


@pytest.fixture
def broken_harness(monkeypatch):
    """Wrap ``build_harness`` so that the harness's train_step is replaced
    by ``make(step)``."""
    import tpuframe.train as train_mod

    def plant(make):
        orig = train_mod.build_harness

        def build(cfg):
            h = orig(cfg)
            return dataclasses.replace(h, train_step=make(h.train_step))

        monkeypatch.setattr(train_mod, "build_harness", build)

    return plant


@pytest.mark.parametrize("workload", ["lm_toy.toy_train_b4_s128",
                                      "resnet_toy.toy_train_b8"])
def test_state_returned_unchanged(bench, broken_harness, workload):
    import jax

    def make(step):
        def stuck(state, batch):
            new, metrics = step(jax.tree.map(lambda x: x.copy(), state),
                                batch)
            return state, metrics
        return stuck

    broken_harness(make)
    r = _run(bench, workload)
    assert r["correct"] is False
    assert "delta3_norm_gap" in _failed(r)
    assert r["compared"]["delta3_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", ["lm_toy.toy_train_b4_s128",
                                      "resnet_toy.toy_train_b8"])
def test_half_of_the_batch_left_out(bench, broken_harness, workload):
    import jax.numpy as jnp

    def make(step):
        def half(state, batch):
            # the second half repeats the first: the mean is over half
            rows = {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2)
                    for k, v in batch.items()}
            return step(state, rows)
        return half

    broken_harness(make)
    r = _run(bench, workload)
    assert r["correct"] is False and _failed(r)


def test_served_token_altered(bench, monkeypatch):
    from tpuframe.serve import engine as engine_mod

    orig = engine_mod.LMEngine.decode_step

    def altered(self):
        toks = np.array(orig(self))
        toks[::2] = (toks[::2] + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(engine_mod.LMEngine, "decode_step", altered)
    r = _run(bench, "lm_toy.toy_serve", seconds=3.0)
    assert r["correct"] is False
    assert "served_token_gap_max" in _failed(r)


def test_request_never_finished(bench, monkeypatch):
    from tpuframe.serve import scheduler as sched_mod

    with open(os.path.join(BENCH, "tests", "toy", "traffic",
                           "toy_serve.json")) as f:
        traffic = json.load(f)
    assert traffic["drain_s"] <= 30
    # the second request of the window: rids count on from the lead-in's
    lg = bench.load_module(os.path.join(BENCH, "loadgen.py"))
    stuck = sum(1 for p in lg.schedule(traffic, 5, 2.0, 512,
                                       lead_s=traffic["lead_s"])
                if p.due_s < 0.0) + 1
    orig = sched_mod.Scheduler._finished

    def never(self, req, tok):
        return False if req.rid == stuck else orig(self, req, tok)

    monkeypatch.setattr(sched_mod.Scheduler, "_finished", never)
    r = _run(bench, "lm_toy.toy_serve", seconds=2.0)
    assert r["correct"] is False and r["failed"] >= 1


@pytest.mark.parametrize("config,traffic", [
    ("lm_toy", "toy_train_b4_s128"), ("resnet_toy", "toy_train_b8")])
def test_training_control_in_int8_is_not_correct(bench, config, traffic):
    """The reference in the program's place, computed in int8."""
    import jax

    toy = os.path.join(BENCH, "tests", "toy")
    with open(os.path.join(toy, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(toy, "traffic", traffic + ".json")) as f:
        t = json.load(f)
    ref = bench.load_module(os.path.join(BENCH, "reference",
                                         cfg["reference"] + ".py"))
    runner = bench.load_module(os.path.join(BENCH, "runners", "train.py"))
    arch, job = cfg["arch"], t["job"]
    weights = ref.init_weights(arch, 11)
    rng = np.random.default_rng(11)
    b = job["global_batch"]
    if config == "lm_toy":
        ids = rng.integers(0, arch["vocab_size"], size=(3, b, 129))
        batches = [{"input_ids": jax.numpy.asarray(x[:, :-1], "int32"),
                    "labels": jax.numpy.asarray(x[:, 1:], "int32")}
                   for x in ids]
    else:
        s = arch["image_size"]
        batches = [{"image": jax.numpy.asarray(
            rng.normal(0.5, 0.25, (b, s, s, 3)), "float32"),
            "label": jax.numpy.asarray(rng.integers(0, 1000, b), "int32")}
            for _ in range(3)]
    good = runner.reference_readings(ref, arch, job, weights, batches)
    low = runner.reference_readings(ref, arch, job, weights, batches,
                                    quant="int8")
    cmp = runner.compare(low, good, t["limits"])
    assert [k for k, v in cmp.items() if not v["value"] <= v["limit"]]
    same = runner.compare(good, good, t["limits"])
    assert all(v["value"] == 0 for v in same.values())


def test_serving_control_in_int8_is_not_correct(bench):
    toy = os.path.join(BENCH, "tests", "toy")
    with open(os.path.join(toy, "configs", "lm_toy.json")) as f:
        arch = json.load(f)["arch"]
    with open(os.path.join(toy, "traffic", "toy_serve.json")) as f:
        limit = json.load(f)["limits"]["served_token_gap_max"]
    import jax.numpy as jnp

    ref = bench.load_module(os.path.join(BENCH, "reference", "lm124m.py"))
    params = ref.init_weights(arch, 3)["params"]
    ids = np.random.default_rng(3).integers(0, arch["vocab_size"],
                                            size=(1, 64))
    gaps, control = ref.make_gap_fn(arch, quant="int8")(
        params, jnp.asarray(ids, jnp.int32))
    assert float(np.max(np.asarray(control))) > limit
