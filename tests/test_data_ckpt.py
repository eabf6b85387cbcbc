"""Data pipeline + checkpoint tests (SURVEY.md §7 test strategy: the fake
cluster exercises host-sharding; golden restore/reshard invariants)."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuframe import ckpt
from tpuframe.data import ArrayDataset, ShardedLoader, cifar10, glue_sst2, mnist
from tpuframe.data import gcs
from tpuframe.parallel import mesh as mesh_lib, step as step_lib


class TestDatasets:
    def test_synthetic_mnist_shapes(self):
        train, test = mnist()
        assert train[0]["image"].shape == (28, 28, 1)
        assert train[:4]["image"].shape == (4, 28, 28, 1)
        assert train[:4]["label"].dtype == np.int32
        assert len(test) < len(train)

    def test_synthetic_cifar_and_glue(self):
        train, _ = cifar10()
        assert train[:2]["image"].shape == (2, 32, 32, 3)
        train, _ = glue_sst2(seq_len=64)
        b = train[:3]
        assert b["input_ids"].shape == (3, 64)
        assert set(b) == {"input_ids", "attention_mask", "token_type_ids", "label"}

    def test_lm_text_padded_docs(self):
        from tpuframe.data.datasets import lm_text

        train, _ = lm_text(seq_len=32, vocab_size=64, synthetic_size=16,
                           padded_docs=True, pad_id=0)
        b = train[:16]
        ids, labels = b["input_ids"], b["labels"]
        assert ids.shape == (16, 32) and labels.shape == (16, 32)
        for i in range(16):
            ignored = np.where(labels[i] == -100)[0]
            assert len(ignored) > 0  # every doc shorter than seq_len+1
            lo = ignored[0]
            # ignore region is a suffix; ids padded with pad_id after it
            assert np.all(labels[i, lo:] == -100)
            np.testing.assert_array_equal(ids[i, lo + 1:],
                                          np.zeros(31 - lo, np.int32))
            # valid region still the shifted next-token targets
            np.testing.assert_array_equal(labels[i, :lo], ids[i, 1:lo + 1])
        with pytest.raises(ValueError, match="synthetic"):
            lm_text("/tmp/x", padded_docs=True)

    def test_shard_disjoint_and_equal(self):
        ds = ArrayDataset({"x": np.arange(103)})
        shards = [ds.shard(4, i) for i in range(4)]
        assert all(len(s) == 25 for s in shards)  # drop remainder
        seen = np.concatenate([s.columns["x"] for s in shards])
        assert len(np.unique(seen)) == 100

    def test_ragged_columns_rejected(self):
        with pytest.raises(ValueError):
            ArrayDataset({"x": np.arange(4), "y": np.arange(5)})

    def test_mnist_idx_file_roundtrip(self, tmp_path):
        """Write real idx-format files and read them back — the on-disk
        format the reference's torchvision MNIST loader consumes."""
        import gzip as gz
        import struct

        imgs = (np.arange(2 * 28 * 28) % 255).astype(np.uint8).reshape(2, 28, 28)
        lbls = np.array([3, 7], np.uint8)

        def idx_bytes(arr):
            header = struct.pack(">I", (0x08 << 0) | (arr.ndim & 0xFF))
            header = struct.pack(">I", 0x00000800 | arr.ndim)
            dims = b"".join(struct.pack(">I", d) for d in arr.shape)
            return header + dims + arr.tobytes()

        for name, arr in [("train-images-idx3-ubyte.gz", imgs),
                          ("train-labels-idx1-ubyte.gz", lbls),
                          ("t10k-images-idx3-ubyte.gz", imgs),
                          ("t10k-labels-idx1-ubyte.gz", lbls)]:
            (tmp_path / name).write_bytes(gz.compress(idx_bytes(arr)))
        train, test = mnist(str(tmp_path))
        assert train[:2]["image"].shape == (2, 28, 28, 1)
        assert float(train[:2]["image"].max()) <= 1.0
        np.testing.assert_array_equal(train[:2]["label"], [3, 7])


class TestShardedLoader:
    def test_batches_sharded_on_mesh(self, mesh8):
        train, _ = mnist(synthetic_size=256)
        loader = ShardedLoader(train, global_batch=32, mesh=mesh8, seed=1)
        batch = next(iter(loader))
        assert batch["image"].shape == (32, 28, 28, 1)
        assert isinstance(batch["image"].sharding, NamedSharding)
        assert batch["image"].sharding.spec == mesh_lib.batch_spec()
        # per-device shard is 4 rows
        assert batch["image"].addressable_shards[0].data.shape[0] == 4

    def test_epoch_determinism_and_reshuffle(self):
        train, _ = mnist(synthetic_size=128)
        a = ShardedLoader(train, 16, seed=7)
        b = ShardedLoader(train, 16, seed=7)
        ba, bb = next(a.epoch(0)), next(b.epoch(0))
        np.testing.assert_array_equal(np.asarray(ba["label"]),
                                      np.asarray(bb["label"]))
        b1 = next(a.epoch(1))
        assert not np.array_equal(np.asarray(ba["label"]), np.asarray(b1["label"]))

    def test_steps_per_epoch_and_divisibility_error(self, mesh8):
        train, _ = mnist(synthetic_size=128)
        loader = ShardedLoader(train, 32, mesh=mesh8)
        assert loader.steps_per_epoch() == 4
        with pytest.raises(ValueError):
            ShardedLoader(train, 12, mesh=mesh8)  # 12 % 8 != 0

    def test_infinite_iter_crosses_epochs(self):
        train, _ = mnist(synthetic_size=64)
        loader = ShardedLoader(train, 32, shuffle=False)
        it = iter(loader)
        seen = [next(it) for _ in range(5)]  # 2 steps/epoch -> crosses twice
        assert len(seen) == 5

    def test_from_step_exact_continuation_across_epoch_boundary(self):
        """Resume positioning (SURVEY.md §5.4): a stream restarted at
        step N must replay the exact remaining batch sequence of an
        uninterrupted run — including the reshuffle at the epoch
        boundary it crosses."""
        train, _ = mnist(synthetic_size=64)
        straight = ShardedLoader(train, 32, seed=5)  # 2 steps/epoch
        it = iter(straight)
        want = [next(it) for _ in range(6)][3:]  # steps 3..5: epochs 1-2
        resumed = ShardedLoader(train, 32, seed=5)
        got_it = resumed.from_step(3)
        got = [next(got_it) for _ in range(3)]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w["label"]),
                                          np.asarray(g["label"]))
            np.testing.assert_array_equal(np.asarray(w["image"]),
                                          np.asarray(g["image"]))

    def test_prefetch_worker_exception_propagates(self):
        """A crash inside the prefetch thread (decoder bug, bad shard)
        must surface in the consumer as the original exception, after
        the batches assembled before it — never a silent hang on an
        empty queue."""
        train, _ = mnist(synthetic_size=64)
        calls = {"n": 0}

        class _FlakyDataset:
            def __len__(self):
                return len(train)

            def __getitem__(self, idx):
                calls["n"] += 1
                if calls["n"] >= 3:
                    raise RuntimeError("decoder blew up")
                return train[idx]

        loader = ShardedLoader(_FlakyDataset(), 16, shuffle=False)
        it = loader.epoch(0)
        next(it), next(it)  # assembled before the fault: still delivered
        with pytest.raises(RuntimeError, match="decoder blew up"):
            for _ in it:
                pass

    def test_cast_floats_halves_infeed_and_matches_device_cast(self):
        import jax.numpy as jnp

        train, _ = mnist(synthetic_size=64)
        plain = next(ShardedLoader(train, 16, shuffle=False).epoch(0))
        cast = next(ShardedLoader(train, 16, shuffle=False,
                                  cast_floats=jnp.bfloat16).epoch(0))
        assert cast["image"].dtype == jnp.bfloat16
        assert cast["label"].dtype == plain["label"].dtype  # ints untouched
        # Host-side numpy rounding == on-device XLA convert (both RNE), so
        # feeding the cast batch is bit-identical to casting after transfer.
        np.testing.assert_array_equal(
            np.asarray(plain["image"].astype(jnp.bfloat16)),
            np.asarray(cast["image"]))

    @pytest.mark.parametrize("case", [
        "shuffled_epochs", "ordered_epochs", "skip", "from_step",
        "callers_dataset_untouched", "float_target_stays_f32",
        "uint8_not_copied", "shard_casts_its_rows_only",
        "other_dataset_cast_by_batch"])
    def test_cast_once_is_the_per_batch_cast_bit_for_bit(self, case,
                                                         monkeypatch):
        """The float ``cast_keys`` column is rounded once a loader, on the
        loader's own copy; every batch reads the bits that rounding the
        gathered float32 rows would give."""
        from tpuframe.obs import timeline

        bf16 = np.dtype(jnp.bfloat16)
        rng = np.random.default_rng(7)
        image = rng.standard_normal((40, 5, 3)).astype(np.float32)
        columns = {"image": image,
                   "label": np.arange(40, dtype=np.int32),
                   "target": rng.standard_normal(40).astype(np.float32)}
        ds = ArrayDataset(dict(columns))

        def check(batches, idxs):
            assert len(batches) == len(idxs)
            for got, idx in zip(batches, idxs):
                want = ds[idx]
                assert got["image"].dtype == bf16
                np.testing.assert_array_equal(
                    np.asarray(got["image"]).view(np.uint16),
                    want["image"].astype(bf16).view(np.uint16))
                np.testing.assert_array_equal(np.asarray(got["label"]),
                                              want["label"])

        def batch_indices(loader, epoch):
            order = loader._epoch_order(epoch)
            return [order[lo:lo + 8] for lo in range(0, 40, 8)]

        if case in ("shuffled_epochs", "ordered_epochs"):
            loader = ShardedLoader(ds, 8, shuffle=case == "shuffled_epochs",
                                   seed=3, cast_floats=jnp.bfloat16)
            for epoch in range(3):
                idxs = batch_indices(loader, epoch)
                if case == "ordered_epochs":
                    assert [list(i) for i in idxs] == [
                        list(range(lo, lo + 8)) for lo in range(0, 40, 8)]
                check(list(loader.epoch(epoch)), idxs)
            assert loader.dataset.columns["image"].dtype == bf16
        elif case == "skip":
            loader = ShardedLoader(ds, 8, seed=3, cast_floats=jnp.bfloat16)
            check(list(loader.epoch(2, skip=3)),
                  batch_indices(loader, 2)[3:])
        elif case == "from_step":
            loader = ShardedLoader(ds, 8, seed=3, cast_floats=jnp.bfloat16)
            stream = loader.from_step(7)       # epoch 1, batch 2, onward
            got = [next(stream) for _ in range(6)]
            loader.close()
            check(got, batch_indices(loader, 1)[2:]
                  + batch_indices(loader, 2)[:3])
        elif case == "callers_dataset_untouched":
            held = ds.columns
            loader = ShardedLoader(ds, 8, cast_floats=jnp.bfloat16)
            list(loader.epoch(0))
            assert ds.columns is held and ds.columns["image"] is image
            assert image.dtype == np.float32
            np.testing.assert_array_equal(image, columns["image"])
            assert loader.dataset is not ds
            assert ds[:2]["image"].dtype == np.float32
            # the columns that were not cast are shared, not copied
            assert loader.dataset.columns["label"] is ds.columns["label"]
        elif case == "float_target_stays_f32":
            loader = ShardedLoader(ds, 8, shuffle=False,
                                   cast_floats=jnp.bfloat16)
            assert loader.dataset.columns["target"] is ds.columns["target"]
            first = next(loader.epoch(0))
            assert first["target"].dtype == np.float32
            np.testing.assert_array_equal(np.asarray(first["target"]),
                                          columns["target"][:8])
        elif case == "uint8_not_copied":
            u8 = ArrayDataset({
                "image": rng.integers(0, 256, (40, 5, 3), dtype=np.uint8),
                "label": columns["label"]})
            t = time.monotonic()
            loader = ShardedLoader(u8, 8, shuffle=False,
                                   cast_floats=jnp.bfloat16)
            assert loader.dataset is u8
            first = next(loader.epoch(0))
            assert first["image"].dtype == np.uint8
            assert timeline.spans("loader.cast_column", t0=t) == []
        elif case == "shard_casts_its_rows_only":
            monkeypatch.setattr(jax, "process_count", lambda: 2)
            monkeypatch.setattr(jax, "process_index", lambda: 1)
            t = time.monotonic()
            loader = ShardedLoader(ds, 16, shuffle=False,
                                   cast_floats=jnp.bfloat16)
            (made,) = timeline.spans("loader.cast_column", t0=t)
            assert made.args == {"key": "image", "rows": 20,
                                 "bytes": 20 * 5 * 3 * 2}
            np.testing.assert_array_equal(
                loader.dataset.columns["image"].view(np.uint16),
                image[20:].astype(bf16).view(np.uint16))
            np.testing.assert_array_equal(loader.dataset.columns["label"],
                                          columns["label"][20:])
        else:
            # a data set that shows no columns: its batches are cast as
            # they come, by the pass the ``loader.cast`` span times
            class Rows:
                def __len__(self):
                    return len(ds)

                def __getitem__(self, idx):
                    return ds[idx]

            t = time.monotonic()
            loader = ShardedLoader(Rows(), 8, shuffle=False,
                                   cast_floats=jnp.bfloat16)
            check(list(loader.epoch(0)), batch_indices(loader, 0))
            assert timeline.spans("loader.cast_column", t0=t) == []


class TestGcsAbstraction:
    def test_local_roundtrip_and_atomicity(self, tmp_path):
        p = str(tmp_path / "a" / "b.bin")
        gcs.write_bytes(p, b"hello")
        assert gcs.read_bytes(p) == b"hello"
        assert gcs.exists(p)
        assert gcs.listdir(str(tmp_path)) == ["a"]
        assert not gcs.exists(str(tmp_path / "nope"))

    def test_gs_scheme_requires_usable_client(self):
        # sandbox has the library but no credentials; either way the error
        # must be our actionable RuntimeError, not a raw client traceback
        with pytest.raises(RuntimeError, match="google-cloud-storage"):
            gcs.read_bytes("gs://bucket/key")

    def test_join(self):
        assert gcs.join("gs://b", "x", "y") == "gs://b/x/y"


def _toy_state(mesh=None):
    tx = optax.adam(1e-3)
    params = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(())}
    state = step_lib.TrainState.create(params, tx)
    if mesh is not None:
        state = step_lib.replicate_state(state, mesh)
    return state


class TestCheckpoint:
    def test_save_restore_exact(self, tmp_path, mesh8):
        state = _toy_state(mesh8)
        ckpt.save(str(tmp_path), 10, state)
        # restore into the exact TrainState structure
        restored = ckpt.restore(str(tmp_path), 10, mesh=mesh8, target=state)
        assert isinstance(restored, step_lib.TrainState)
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(state.params["w"]))
        chex_all_equal_structs(state, restored)

    def test_restore_without_target_gives_nested_dict(self, tmp_path, mesh8):
        state = _toy_state(mesh8)
        ckpt.save(str(tmp_path), 3, state)
        tree = ckpt.restore(str(tmp_path), 3)
        assert isinstance(tree, dict)
        np.testing.assert_array_equal(tree["params"]["w"],
                                      np.asarray(state.params["w"]))

    def test_reshard_on_restore(self, tmp_path, mesh8):
        """Save sharded over 8 devices, restore onto a 4-device mesh —
        SURVEY.md §7 hard part 3 (8-chip ckpt onto 32 chips, scaled down)."""
        big = jnp.arange(64.0).reshape(8, 8)
        sharded = jax.device_put(big, NamedSharding(mesh8, P("data")))
        ckpt.save(str(tmp_path), 1, {"x": sharded})
        assert len({s["file"] for s in json.loads(
            gcs.read_bytes(str(tmp_path / "step_00000001" / "manifest.json"))
        )["leaves"]["x"]["shards"]}) == 8

        mesh4 = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4),
                                   devices=jax.devices()[:4])
        target = {"x": jax.device_put(jnp.zeros((8, 8)),
                                      NamedSharding(mesh4, P("data")))}
        restored = ckpt.restore(str(tmp_path), 1, target=target)
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(big))
        assert restored["x"].sharding.mesh.shape["data"] == 4

    def test_bf16_leaf_roundtrip(self, tmp_path, mesh8):
        """np.save round-trips ml_dtypes bfloat16 as void records; restore
        must reinterpret via the manifest dtype (code-review finding)."""
        tree = {"p": jnp.arange(6.0, dtype=jnp.bfloat16).reshape(2, 3)}
        ckpt.save(str(tmp_path), 1, tree)
        out = ckpt.restore(str(tmp_path), 1)
        assert out["p"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out["p"], np.float32),
                                      np.arange(6.0).reshape(2, 3))

    def test_sharded_restore_reads_only_overlapping_shards(self, tmp_path, mesh8):
        """Sharded-target restore goes through the region reader."""
        big = jnp.arange(64.0).reshape(8, 8)
        sharded = jax.device_put(big, NamedSharding(mesh8, P("data")))
        ckpt.save(str(tmp_path), 1, {"x": sharded})
        target = {"x": sharded}
        restored = ckpt.restore(str(tmp_path), 1, target=target)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(big))
        assert not restored["x"].sharding.is_fully_replicated

    def test_crc_detects_corruption(self, tmp_path, mesh8):
        state = _toy_state(mesh8)
        path = ckpt.save(str(tmp_path), 5, state)
        # corrupt one shard file
        victim = next(f for f in (tmp_path / "step_00000005").iterdir()
                      if f.name.endswith(".npy"))
        raw = bytearray(victim.read_bytes())
        raw[-1] ^= 0xFF
        victim.write_bytes(bytes(raw))
        with pytest.raises(IOError, match="CRC"):
            ckpt.restore(str(tmp_path), 5, mesh=mesh8, target=state)

    def test_structure_mismatch_raises(self, tmp_path, mesh8):
        state = _toy_state(mesh8)
        ckpt.save(str(tmp_path), 2, state)
        bad_target = {"nope": jnp.zeros(())}
        with pytest.raises(ValueError, match="structure mismatch"):
            ckpt.restore(str(tmp_path), 2, target=bad_target)

    def test_manager_retention_resume_and_torn_ckpt(self, tmp_path, mesh8):
        state = _toy_state(mesh8)
        mgr = ckpt.CheckpointManager(str(tmp_path), every_steps=10, keep=2)
        assert not mgr.should_save(5)
        for step in (10, 20, 30):
            assert mgr.maybe_save(step, state) is not None
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["step_00000020", "step_00000030"]  # keep=2
        # torn checkpoint (no COMMIT) must be ignored by resume
        torn = tmp_path / "step_00000040"
        torn.mkdir()
        (torn / "manifest.json").write_text("{}")
        step, restored = mgr.restore_latest(mesh=mesh8, target=state)
        assert step == 30
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(state.params["w"]))

    def test_restore_latest_empty(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path))
        assert mgr.restore_latest() is None

    def test_save_best_keeps_single_record(self, tmp_path, mesh8):
        """save_best: only improvements are kept, exactly one best dir
        exists, restore_best returns the winning step's state."""
        mgr = ckpt.CheckpointManager(str(tmp_path), every_steps=10)
        s1 = _toy_state(mesh8)
        s2 = jax.tree_util.tree_map(
            lambda a: a * 2 if jnp.issubdtype(a.dtype, jnp.floating) else a,
            s1)
        assert mgr.save_best(10, s1, 1.5) is True
        assert mgr.save_best(20, s2, 2.0) is False   # worse: not saved
        assert mgr.save_best(30, s2, 0.5) is True    # better: replaces
        best_dirs = [p.name for p in (tmp_path / "best").iterdir()
                     if p.is_dir()]
        assert best_dirs == ["step_00000030"]
        step, restored = mgr.restore_best(mesh=mesh8, target=s1)
        assert step == 30
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(s2.params["w"]))
        # max mode: higher wins
        mgr2 = ckpt.CheckpointManager(str(tmp_path / "m2"))
        assert mgr2.save_best(1, s1, 0.7, mode="max") is True
        assert mgr2.save_best(2, s2, 0.6, mode="max") is False
        step2, _ = mgr2.restore_best(mesh=mesh8, target=s1)
        assert step2 == 1
        with pytest.raises(ValueError, match="contradicts"):
            mgr2.save_best(3, s1, 0.1, mode="min")  # opposite-order record
        with pytest.raises(ValueError, match="mode"):
            mgr2.save_best(3, s1, 0.1, mode="best")

    def test_async_save_commits_and_roundtrips(self, tmp_path, mesh8):
        """async_write: save() returns before COMMIT; wait_pending() makes
        every queued save durable, in order, with retention applied; the
        snapshot is immune to the live tree changing after save()."""
        state = _toy_state(mesh8)
        mgr = ckpt.CheckpointManager(str(tmp_path), every_steps=10, keep=2,
                                     async_write=True)
        saved_w = np.array(np.asarray(state.params["w"]), copy=True)
        for step in (10, 20, 30):
            mgr.maybe_save(step, state)
            # mutate the live tree right after the snapshot — the async
            # writer must not see this (copy-on-prepare contract)
            state = jax.tree_util.tree_map(
                lambda a: a + 1.0
                if jnp.issubdtype(a.dtype, jnp.floating) else a, state)
        mgr.wait_pending()
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["step_00000020", "step_00000030"]  # keep=2
        assert (tmp_path / "step_00000030" / "COMMIT").exists()
        step, restored = mgr.restore_latest(mesh=mesh8,
                                            target=_toy_state(mesh8))
        assert step == 30
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      saved_w + 2.0)  # state at save #3


def chex_all_equal_structs(a, b):
    ja = jax.tree_util.tree_structure(a)
    jb = jax.tree_util.tree_structure(b)
    assert ja == jb, (ja, jb)


class TestPrepareImagenet:
    def _make_tree(self, root, n_classes=2, per_class=3):
        from PIL import Image

        rng = np.random.default_rng(0)
        for c in range(n_classes):
            d = root / f"n{c:08d}"
            d.mkdir(parents=True)
            for i in range(per_class):
                arr = rng.integers(0, 255, size=(40 + 8 * c, 64, 3),
                                   dtype=np.uint8)
                Image.fromarray(arr).save(d / f"img_{i}.JPEG")

    def test_prepare_and_load_roundtrip(self, tmp_path):
        from tpuframe.data import prepare_imagenet
        from tpuframe.data.datasets import imagenet

        src, out = tmp_path / "raw", tmp_path / "out"
        self._make_tree(src)
        n = prepare_imagenet.prepare(str(src), str(out), image_size=32,
                                     shard_size=4, workers=1)
        assert n == 2  # 6 examples, shard_size 4 -> 2 shards
        names = sorted(p.name for p in out.iterdir())
        assert "images_00000.npy" in names and "labels_00001.npy" in names
        assert "classes.txt" in names

        train, test = imagenet(str(out), image_size=32)
        total = len(train) + len(test)
        assert total == 6
        img = train[:1]["image"]
        assert img.dtype == np.float32 and img.shape[1:] == (32, 32, 3)
        # normalized: values centered near 0, not 0..255
        assert abs(float(img.mean())) < 3.0

    def test_decode_geometry(self, tmp_path):
        from PIL import Image

        from tpuframe.data import prepare_imagenet

        p = tmp_path / "x.jpg"
        Image.fromarray(np.zeros((100, 300, 3), np.uint8)).save(p)
        arr = prepare_imagenet.decode_one((str(p), 64, 0))
        assert arr.shape == (64, 64, 3) and arr.dtype == np.uint8


class TestAugment:
    """On-device augmentation (tpuframe/data/augment.py)."""

    def test_flip_is_per_image_and_deterministic(self):
        import jax
        import jax.numpy as jnp
        from tpuframe.data import augment

        imgs = jnp.arange(4 * 2 * 3 * 1, dtype=jnp.uint8).reshape(4, 2, 3, 1)
        a = augment.random_flip(imgs, jax.random.key(0))
        b = augment.random_flip(imgs, jax.random.key(0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        flipped = np.asarray(a) != np.asarray(imgs)
        per_img = flipped.reshape(4, -1).any(axis=1)
        assert per_img.any()          # some flip...
        assert not per_img.all() or True  # (p=0.5 over 4: both possible)
        # a flipped image is exactly the W-reverse
        for i in range(4):
            if per_img[i]:
                np.testing.assert_array_equal(
                    np.asarray(a)[i], np.asarray(imgs)[i, :, ::-1, :])

    def test_pad_crop_flip_preserves_shape_and_content_bounds(self):
        import jax
        import jax.numpy as jnp
        from tpuframe.data import augment

        imgs = jnp.ones((8, 32, 32, 3), jnp.uint8) * 7
        out = augment.apply("pad_crop_flip", imgs, jax.random.key(1))
        assert out.shape == imgs.shape and out.dtype == imgs.dtype
        vals = set(np.unique(np.asarray(out)).tolist())
        assert vals <= {0, 7}          # original pixels or zero padding

    def test_crop_flip_requires_margin(self):
        import jax
        import jax.numpy as jnp
        import pytest as _pytest
        from tpuframe.data import augment

        imgs = jnp.zeros((2, 32, 32, 3), jnp.uint8)
        with _pytest.raises(ValueError, match="smaller"):
            augment.apply("crop_flip", imgs, jax.random.key(0), crop=64)
        out = augment.apply("crop_flip",
                            jnp.zeros((2, 40, 40, 3), jnp.uint8),
                            jax.random.key(0), crop=32)
        assert out.shape == (2, 32, 32, 3)

    def test_unknown_mode_raises(self):
        import jax
        import jax.numpy as jnp
        import pytest as _pytest
        from tpuframe.data import augment

        with _pytest.raises(ValueError, match="unknown augment"):
            augment.apply("mixup", jnp.zeros((1, 8, 8, 3)),
                          jax.random.key(0))

    def test_center_crop_matches_geometry(self):
        import jax.numpy as jnp
        from tpuframe.data import augment

        imgs = jnp.arange(2 * 8 * 8 * 1, dtype=jnp.float32).reshape(2, 8, 8, 1)
        out = augment.center_crop(imgs, 4)
        assert out.shape == (2, 4, 4, 1)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(imgs[:, 2:6, 2:6, :]))
        # size-match is the identity
        same = augment.center_crop(imgs, 8)
        np.testing.assert_array_equal(np.asarray(same), np.asarray(imgs))
        import pytest as _pytest
        with _pytest.raises(ValueError, match="smaller"):
            augment.center_crop(imgs, 16)

    def test_crop_flip_end_to_end_harness(self):
        """Train 2 steps with larger synthetic storage + crop_flip: train
        crops to augment_crop, eval center-crops — both paths compile."""
        from tpuframe import train as train_mod
        from tpuframe.utils import get_config

        cfg = get_config("imagenet_resnet50").with_overrides(
            total_steps=2, eval_every=2, eval_batches=1, global_batch=16,
            warmup_steps=1, log_every=1, compute_dtype="float32",
            augment="crop_flip", augment_crop=24,
            dataset_kwargs={"image_size": 32, "synthetic_size": 32,
                            "num_classes": 10},
            model_kwargs={"cifar_stem": True, "num_classes": 10})
        metrics = train_mod.train(cfg)
        assert np.isfinite(metrics["loss"])
