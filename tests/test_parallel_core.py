"""Core distributed tests: mesh construction, collectives, hvd facade, step.

Mirrors the reference's implicit invariants (SURVEY.md §7 test strategy):
the golden DP-correctness test — N-device gradients must equal 1-device
gradients on the same global batch — is the SPMD analog of Horovod's
allreduce-averaging contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from tpuframe.parallel import collectives, hvd, step as step_lib
from tpuframe.parallel import mesh as mesh_lib


class TestMesh:
    def test_default_mesh_is_pure_dp(self, mesh8):
        assert mesh8.shape["data"] == 8
        for ax in mesh_lib.AXES[1:]:
            assert mesh8.shape[ax] == 1
        assert mesh_lib.data_parallel_size(mesh8) == 8

    def test_wildcard_resolution(self):
        sizes = mesh_lib.MeshSpec(data=-1, model=2).sizes(8)
        assert sizes["data"] == 4 and sizes["model"] == 2

    def test_bad_divisibility_raises(self):
        with pytest.raises(ValueError):
            mesh_lib.MeshSpec(data=3).sizes(8)
        with pytest.raises(ValueError):
            mesh_lib.MeshSpec(data=-1, model=-1).sizes(8)

    def test_mesh42(self, mesh42):
        assert mesh42.shape["data"] == 4 and mesh42.shape["model"] == 2
        assert mesh_lib.data_parallel_size(mesh42) == 4

    def test_local_batch_size(self, mesh8):
        assert mesh_lib.local_batch_size(mesh8, 64) == 64  # single host
        with pytest.raises(ValueError):
            mesh_lib.local_batch_size(mesh8, 13)


class TestCollectives:
    def test_allreduce_mean_sum(self, mesh8):
        def body(x):
            return (collectives.allreduce(x, "data", average=True),
                    collectives.allreduce(x, "data", average=False))

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=(P(), P())))
        x = np.arange(8.0)
        mean, total = f(x)
        assert mean[0] == pytest.approx(3.5)
        assert total[0] == pytest.approx(28.0)

    def test_allreduce_identity_unmapped(self):
        x = jnp.ones((3,))
        np.testing.assert_array_equal(collectives.allreduce(x), x)

    def test_broadcast_root(self, mesh8):
        def body(x):
            return collectives.broadcast(x, "data", root=3)

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P("data")))
        out = f(np.arange(8.0))
        np.testing.assert_array_equal(np.asarray(out), np.full(8, 3.0))

    def test_allgather(self, mesh8):
        def body(x):
            return collectives.allgather(x, "data")

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P("data")))
        out = np.asarray(f(np.arange(8.0))).reshape(8, 8)
        np.testing.assert_array_equal(out[0], np.arange(8.0))

    def test_ring_permute(self, mesh8):
        def body(x):
            return collectives.ring_permute(x, "data", shift=1)

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P("data")))
        out = np.asarray(f(np.arange(8.0)))
        np.testing.assert_array_equal(out, np.roll(np.arange(8.0), 1))

    def test_alltoall(self, mesh8):
        def body(x):
            return collectives.alltoall(x, "data", split_axis=0, concat_axis=0)

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P("data")))
        x = np.arange(64.0).reshape(64, 1)  # 8 rows/shard, split 8 ways
        out = np.asarray(f(x)).reshape(8, 8)
        # shard i row j == shard j row i of input blocks
        blocks = x.reshape(8, 8)
        np.testing.assert_array_equal(out, blocks.T)

    def test_reduce_scatter(self, mesh8):
        def body(x):
            return collectives.reduce_scatter(x, "data")

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P("data")))
        x = np.ones((64,))  # each shard holds 8 ones
        out = np.asarray(f(x))
        np.testing.assert_array_equal(out, np.full(8, 8.0))

    def test_global_norm_allreduced(self, mesh8):
        def body(x):
            return collectives.global_norm({"g": x}, axis="data")

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P()))
        x = np.ones((8,))
        assert float(f(x)) == pytest.approx(np.sqrt(8.0))

    def test_cross_replica_mean_host_level(self, mesh8):
        out = collectives.cross_replica_mean({"acc": 0.5}, mesh8)
        assert float(out["acc"]) == pytest.approx(0.5)

    def test_allreduce_partial_axis_binding(self):
        """Under pmap only 'data' is bound; allreduce over the default
        ('data','fsdp') must still reduce over the bound subset (code-review
        finding: the all-or-nothing check silently skipped the reduction)."""
        f = jax.pmap(lambda x: collectives.allreduce(x, axis=("data", "fsdp")),
                     axis_name="data")
        out = np.asarray(f(np.arange(8.0)))
        np.testing.assert_allclose(out, np.full(8, 3.5))

    def test_collectives_identity_unmapped(self):
        """allgather/alltoall/ring_permute/reduce_scatter must no-op outside a
        mapped context (single-process mode), like allreduce/broadcast."""
        x = jnp.arange(4.0)
        for fn in (collectives.allgather, collectives.alltoall,
                   collectives.ring_permute, collectives.reduce_scatter,
                   collectives.broadcast):
            np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


_GATHER_MESHES = {
    "data8": mesh_lib.MeshSpec(data=8),
    "data4-fsdp2": mesh_lib.MeshSpec(data=4, fsdp=2),
    "slice2-data4": mesh_lib.MeshSpec(data=4, slices=2),
}


class TestAllgatherInvariant:
    @pytest.mark.parametrize("tiled", [True, False],
                             ids=["tiled", "stacked"])
    @pytest.mark.parametrize("gather_axis", [0, 1])
    @pytest.mark.parametrize("mesh_name", sorted(_GATHER_MESHES))
    def test_full_array_on_every_replica(self, mesh_name, gather_axis,
                                         tiled):
        """Under a replicated out_spec the gather must come back
        invariant — a varying ``lax.all_gather`` is refused there — and
        every replica must hold the whole array."""
        mesh = mesh_lib.make_mesh(_GATHER_MESHES[mesh_name])
        axes = mesh_lib.batch_axes(mesh)
        x = np.arange(16.0 * 24).reshape(16, 24).astype(np.float32)
        in_spec = P(axes) if gather_axis == 0 else P(None, axes)

        def body(block):
            return collectives.allgather_invariant(
                block, axes, gather_axis=gather_axis, tiled=tiled)

        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                    out_specs=P()))(x)
        want = x if tiled else (
            x.reshape(8, 2, 24) if gather_axis == 0
            else x.reshape(16, 8, 3))
        assert len(out.addressable_shards) == 8
        for shard in out.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data), want)

    def test_invariant_gather_lives_where_imported(self):
        """jax 0.9.0 keeps the Varying -> Invariant gather in
        ``jax._src.lax.parallel`` and does not export it under
        ``jax.lax``, so collectives.py imports it from there.  When this
        fails a jax upgrade moved or published the name: point that one
        import at where it lives now."""
        import importlib

        home = importlib.import_module("jax._src.lax.parallel")
        assert collectives._all_gather_invariant is home.all_gather_invariant
        assert not hasattr(lax, "all_gather_invariant"), \
            "jax exports it now: import it from jax.lax in collectives.py"


class TestHvdFacade:
    def test_size_rank(self):
        hvd.init()
        assert hvd.size() == 8
        assert hvd.rank() == 0
        assert hvd.local_rank() == 0
        assert hvd.is_primary()

    def test_allgather_alltoall_grouped_verbs(self, mesh8):
        """The porting-surface extras: hvd.allgather / alltoall /
        grouped_allreduce inside a mapped step; barrier/join/shutdown are
        host-side and exercised single-process."""
        def body(x):
            gathered = hvd.allgather(x, axis=("data",))
            pair = hvd.grouped_allreduce([x, 2 * x], axis=("data",))
            # collective outputs are replica-identical but vma-varying;
            # pmean makes them provably unvarying for the P() out_specs
            return jax.tree.map(lambda t: jax.lax.pmean(t, "data"),
                                (gathered, pair[0], pair[1]))

        f = jax.jit(jax.shard_map(
            body, mesh=mesh8, in_specs=P("data"),
            out_specs=(jax.sharding.PartitionSpec(),) * 3))
        xs = np.arange(8.0, dtype=np.float32)
        gathered, a, b = f(xs)
        np.testing.assert_array_equal(np.asarray(gathered), xs)
        assert float(a[0]) == pytest.approx(3.5)     # mean over replicas
        assert float(b[0]) == pytest.approx(7.0)
        # uniform splits are the static-shape case and must pass through;
        # only genuinely ragged (unequal) splits are rejected
        np.testing.assert_array_equal(
            np.asarray(hvd.alltoall(jnp.arange(8.0), splits=[1] * 8)),
            np.arange(8.0))
        with pytest.raises(NotImplementedError, match="UNEQUAL"):
            hvd.alltoall(jnp.zeros((8,)), splits=[2, 6])
        assert hvd.join() == -1     # barrier-backed; single-process no-op
        hvd.barrier()
        hvd.shutdown()              # idempotent

    def test_distributed_optimizer_averages(self, mesh8):
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), axis=("data",))

        def body(g):
            state = tx.init({"w": jnp.zeros(())})
            updates, _ = tx.update({"w": g}, state, {"w": jnp.zeros(())})
            return updates["w"]

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P()))
        upd = f(np.arange(8.0))
        # sgd(1.0) update = -avg(grad) = -3.5
        assert float(upd[0]) == pytest.approx(-3.5)

    def test_distributed_optimizer_identity_unmapped(self):
        tx = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(())}
        state = tx.init(params)
        updates, _ = tx.update({"w": jnp.ones(())}, state, params)
        assert float(updates["w"]) == pytest.approx(-0.1)

    def test_distributed_optimizer_with_autodiff_grads(self, mesh8):
        """Grads from jax.grad w.r.t. replicated params arrive pre-psum'd
        (vma-unvarying); DistributedOptimizer must still produce the average,
        matching hvd semantics exactly."""
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), axis=("data",))

        def body(w, xs):
            g = jax.grad(lambda w: jnp.mean(w * xs))(w)  # pre-summed by vma
            state = tx.init(w)
            updates, _ = tx.update(g, state, w)
            return updates

        f = jax.jit(jax.shard_map(body, mesh=mesh8,
                                  in_specs=(P(), P("data")), out_specs=P()))
        xs = np.arange(32.0, dtype=np.float32)
        upd = f(jnp.zeros(()), xs)
        # average grad = mean(xs) = 15.5 → sgd(1.0) update = -15.5
        assert float(upd) == pytest.approx(-15.5)

    def test_distributed_optimizer_sum_not_double_counted(self, mesh8):
        """average=False with autodiff (pre-psum'd) grads must give the sum
        once, not world_size× (code-review finding)."""
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), axis=("data",),
                                      average=False)

        def body(w, xs):
            g = jax.grad(lambda w: jnp.mean(w * xs))(w)  # pre-summed
            state = tx.init(w)
            updates, _ = tx.update(g, state, w)
            return updates

        f = jax.jit(jax.shard_map(body, mesh=mesh8,
                                  in_specs=(P(), P("data")), out_specs=P()))
        xs = np.arange(32.0, dtype=np.float32)
        upd = f(jnp.zeros(()), xs)
        # sum of per-shard grads = sum of local means = 8 * 15.5 = 124
        assert float(upd) == pytest.approx(-124.0)

    def test_bf16_compression_preserves_native_bf16(self, mesh8):
        """bf16-native grads must come back bf16, not upcast to f32
        (code-review finding: decompress keyed on dtype, not provenance)."""
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), axis=("data",),
                                      compression="bf16")

        def body(g):
            params = {"w": jnp.zeros((), jnp.bfloat16)}
            state = tx.init(params)
            updates, _ = tx.update({"w": g}, state, params)
            return updates["w"]

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P()))
        out = f(np.full(8, 2.0, np.float32).astype(jnp.bfloat16))
        assert out.dtype == jnp.bfloat16

    def test_bf16_compression_roundtrip(self, mesh8):
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), axis=("data",),
                                      compression="bf16")

        def body(g):
            state = tx.init({"w": jnp.zeros(())})
            updates, _ = tx.update({"w": g}, state, {"w": jnp.zeros(())})
            return updates["w"]

        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P()))
        upd = f(np.full(8, 2.0))
        assert upd.dtype == jnp.float32
        assert float(upd[0]) == pytest.approx(-2.0)


def _toy_loss(params, model_state, batch, rng):
    del rng
    x, y = batch["x"], batch["y"]
    pred = x @ params["w"] + params["b"]
    loss = jnp.mean((pred - y) ** 2)
    return loss, (model_state, {"mse": loss})


def _toy_batch(n=32, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = np.arange(d, dtype=np.float32)
    y = x @ w + 0.1 * rng.normal(size=(n,)).astype(np.float32)
    return {"x": x, "y": y}


class TestTrainStep:
    def _init_state(self, tx, d=4):
        params = {"w": jnp.zeros((d,)), "b": jnp.zeros(())}
        return step_lib.TrainState.create(params, tx)

    def test_golden_dp_equals_single_device(self, mesh8):
        """THE DP-correctness invariant (SURVEY.md §7): same global batch,
        same seed ⇒ 8-way sharded step produces identical params to the
        unsharded step."""
        tx = optax.sgd(0.05)
        batch = _toy_batch()

        single = step_lib.make_train_step(_toy_loss, tx, None, donate=False)
        dist = step_lib.make_train_step(_toy_loss, tx, mesh8, donate=False)

        s1, m1 = single(self._init_state(tx), batch)
        s8, m8 = dist(self._init_state(tx), batch)

        np.testing.assert_allclose(np.asarray(s1.params["w"]),
                                   np.asarray(s8.params["w"]), rtol=1e-5)
        np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-5)
        assert int(s8.step) == 1

    def test_jit_mode_matches_shard_map(self, mesh8):
        tx = optax.sgd(0.05)
        batch = _toy_batch()
        a = step_lib.make_train_step(_toy_loss, tx, mesh8, mode="shard_map",
                                     donate=False)
        b = step_lib.make_train_step(_toy_loss, tx, mesh8, mode="jit",
                                     donate=False)
        sa, _ = a(self._init_state(tx), batch)
        sb, _ = b(self._init_state(tx), batch)
        np.testing.assert_allclose(np.asarray(sa.params["w"]),
                                   np.asarray(sb.params["w"]), rtol=1e-5)

    def test_loss_decreases(self, mesh8):
        tx = optax.sgd(0.1)
        train = step_lib.make_train_step(_toy_loss, tx, mesh8, donate=False)
        state = self._init_state(tx)
        batch = _toy_batch()
        losses = []
        for _ in range(20):
            state, m = train(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.1 * losses[0]

    def test_eval_step_averages(self, mesh8):
        def metric_fn(params, model_state, batch):
            return {"mean_y": jnp.mean(batch["y"])}

        ev = step_lib.make_eval_step(metric_fn, mesh8)
        tx = optax.sgd(0.1)
        state = self._init_state(tx)
        batch = _toy_batch()
        out = ev(state, batch)
        assert float(out["mean_y"]) == pytest.approx(float(np.mean(batch["y"])),
                                                     rel=1e-5)

    def test_collectives_in_compiled_program(self, mesh8):
        """The compiled DP step must actually contain an all-reduce — the
        SPMD analog of asserting NCCL was invoked."""
        tx = optax.sgd(0.05)
        train = step_lib.make_train_step(_toy_loss, tx, mesh8, donate=False)
        state = self._init_state(tx)
        batch = _toy_batch()
        compiled = train.lower(state, batch).compile()
        hlo = compiled.as_text()
        assert "all-reduce" in hlo


@pytest.mark.parametrize("spelling", ["make_train_step(wire_format)",
                                      "pspec.lower(wire_format_dcn)",
                                      "compression=int8"])
def test_removed_wire_spellings_are_refused(mesh8, spelling):
    """The int8-block wire is gone without a shim: its keywords are
    Python's TypeError and its compression name is an unknown one."""
    from tpuframe.parallel import pspec

    tx = optax.sgd(0.05)
    if spelling == "make_train_step(wire_format)":
        with pytest.raises(TypeError, match="wire_format"):
            step_lib.make_train_step(_toy_loss, tx, mesh8,
                                     wire_format="int8-block")
    elif spelling == "pspec.lower(wire_format_dcn)":
        spec = pspec.parse_spec("dp=4;slices=2")
        with pytest.raises(TypeError, match="wire_format_dcn"):
            pspec.lower(spec, spec.make_mesh(), hier="hier",
                        wire_format_dcn="int8-block")
    else:
        opt = hvd.DistributedOptimizer(tx, compression="int8")
        grads = {"w": jnp.ones((4,))}
        with pytest.raises(ValueError, match="unknown compression 'int8'"):
            opt.update(grads, opt.init(grads))
