"""FSDP/ZeRO sharding: golden-loss vs replicated DP + placement checks
(SURVEY.md §7 golden-loss strategy; PAPERS.md:5 weight-update sharding)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpuframe import models
from tpuframe.models import losses
from tpuframe.parallel import fsdp as fsdp_lib
from tpuframe.parallel import mesh as mesh_lib
from tpuframe.parallel import step as step_lib


def _setup(mesh, use_fsdp):
    model = models.get_model("transformer-lm", tiny=True, vocab_size=64,
                             max_seq=32)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, size=(8, 33)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    variables = model.init(jax.random.key(0),
                           jnp.asarray(batch["input_ids"][:1]))
    tx = optax.adamw(1e-3)

    def loss_fn(params, model_state, b, rng):
        logits = model.apply({"params": params}, b["input_ids"], train=True,
                             rngs={"dropout": rng})
        return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

    state = step_lib.TrainState.create(variables["params"], tx)
    shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        if use_fsdp:
            shardings = fsdp_lib.state_shardings(state, mesh)
            state = jax.tree.map(jax.device_put, state, shardings)
        else:
            state = step_lib.replicate_state(state, mesh)
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, mesh_lib.batch_spec())), batch)
    step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                    state_shardings=shardings)
    return state, step, batch


def _losses(mesh, use_fsdp, n=3):
    state, step, batch = _setup(mesh, use_fsdp)
    out = []
    for _ in range(n):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out, state


@pytest.mark.slow
def test_fsdp_golden_loss_vs_replicated():
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=2, fsdp=4))
    ref, _ = _losses(None, False)
    got, _ = _losses(mesh, True)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert ref[-1] < ref[0]


def test_fsdp_state_actually_sharded():
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=2, fsdp=4))
    _, state = _losses(mesh, True, n=1)
    frac = fsdp_lib.param_fraction_sharded(state.params)
    assert frac > 0.9, f"only {frac:.1%} of param elements fsdp-sharded"
    # Optimizer moments mirror param sharding (the ZeRO memory win).
    frac_opt = fsdp_lib.param_fraction_sharded(state.opt_state)
    assert frac_opt > 0.5, f"only {frac_opt:.1%} of opt state sharded"
    # Per-device bytes: a sharded leaf stores 1/4 of its elements per chip.
    leaf = state.params["block_0"]["attn"]["query"]["kernel"]
    shard_shape = leaf.sharding.shard_shape(leaf.shape)
    assert int(np.prod(shard_shape)) == int(np.prod(leaf.shape)) // 4


def test_choose_spec_rules():
    assert fsdp_lib.choose_spec((4096, 512), 4) == P("fsdp", None)
    assert fsdp_lib.choose_spec((512, 4096), 4) == P(None, "fsdp")
    assert fsdp_lib.choose_spec((3, 5), 4) == P()        # tiny → replicated
    assert fsdp_lib.choose_spec((4098, 2), 4) == P()     # indivisible
    assert fsdp_lib.choose_spec((4096,), 1) == P()       # no fsdp axis


class TestTensorParallel:
    """TP over the model axis (tpuframe.parallel.tp) — golden loss +
    placement; composition with fsdp."""

    def _setup_tp(self, mesh_spec, model_kwargs=None):
        from tpuframe.parallel import tp as tp_lib

        mesh = mesh_lib.make_mesh(mesh_spec) if mesh_spec else None
        model = models.get_model("transformer-lm", tiny=True, vocab_size=64,
                                 max_seq=32, **(model_kwargs or {}))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, size=(8, 33)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        variables = model.init(jax.random.key(0),
                               jnp.asarray(batch["input_ids"][:1]))
        tx = optax.adamw(1e-3)

        def loss_fn(params, model_state, b, rng):
            logits = model.apply({"params": params}, b["input_ids"],
                                 train=True, rngs={"dropout": rng})
            return losses.softmax_cross_entropy(logits, b["labels"]), ({}, {})

        state = step_lib.TrainState.create(variables["params"], tx)
        shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            rules = tp_lib.rules_for_model("transformer-lm")
            shardings = fsdp_lib.state_shardings(state, mesh, tp_rules=rules)
            state = jax.tree.map(jax.device_put, state, shardings)
            batch = jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, mesh_lib.batch_spec())), batch)
        step = step_lib.make_train_step(loss_fn, tx, mesh, donate=False,
                                        state_shardings=shardings)
        return state, step, batch

    def _losses(self, mesh_spec, n=3):
        state, step, batch = self._setup_tp(mesh_spec)
        out = []
        for _ in range(n):
            state, m = step(state, batch)
            out.append(float(m["loss"]))
        return out, state

    def test_tp_golden_loss_vs_single_device(self):
        ref, _ = self._losses(None)
        got, _ = self._losses(mesh_lib.MeshSpec(data=2, model=4))
        np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)
        assert ref[-1] < ref[0]

    def test_tp_params_sharded_by_heads(self):
        _, state = self._losses(mesh_lib.MeshSpec(data=2, model=4), n=1)
        qk = state.params["block_0"]["attn"]["query"]["kernel"]
        # [hidden, heads, head_dim] with heads=4 split over model=4
        assert qk.sharding.shard_shape(qk.shape)[1] == qk.shape[1] // 4
        up = state.params["block_0"]["up"]["kernel"]
        assert up.sharding.shard_shape(up.shape)[1] == up.shape[1] // 4

    def test_tp_fsdp_compose(self):
        ref, _ = self._losses(None)
        got, state = self._losses(mesh_lib.MeshSpec(data=2, fsdp=2, model=2))
        np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)
        qk = state.params["block_0"]["attn"]["query"]["kernel"]
        shard = qk.sharding.shard_shape(qk.shape)
        # model splits heads (dim 1), fsdp overlays the largest free dim
        assert int(np.prod(shard)) == int(np.prod(qk.shape)) // 4

    def test_match_spec_indivisible_falls_back(self):
        from jax.sharding import PartitionSpec as P

        from tpuframe.parallel import tp as tp_lib

        rules = tp_lib.rules_for_model("transformer-lm")
        # 3 heads not divisible by 4 -> replicate, never crash
        assert tp_lib.match_spec("block_0/attn/query/kernel", (64, 3, 16),
                                 4, rules) is None
        assert tp_lib.match_spec("block_0/attn/query/kernel", (64, 4, 16),
                                 4, rules) == P(None, "model", None)
        assert tp_lib.match_spec("block_0/mlp_ln/scale", (64,), 4,
                                 rules) is None
