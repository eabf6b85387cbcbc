"""Host→device input pipeline with per-host sharding and prefetch.

Reference path (SURVEY.md §4.1): torch DataLoader worker processes feed
per-rank batches; each rank's DataLoader holds a DistributedSampler shard.
TPU-native path: each *host* process iterates its shard of the dataset and
device_puts batches pre-sharded over the mesh's batch axes, one step ahead of
compute (double buffering) so infeed overlaps the running step — the role
Horovod leaves to DataLoader prefetch + CUDA streams.

Batch assembly inside the prefetch thread uses the multi-threaded C++ row
gather from ``tpuframe.native`` (GIL-released; see ArrayDataset.__getitem__),
with numpy fancy-indexing as the fallback when the native library is
unavailable.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from tpuframe.data.datasets import ArrayDataset
from tpuframe.obs import metrics, timeline
from tpuframe.parallel import mesh as mesh_lib


class ShardedLoader:
    """Iterates epoch-shuffled, host-sharded, device-put batches.

    Parameters
    ----------
    dataset: the FULL (logical) dataset; every host passes the same one and
        takes its shard internally — keeps the call site identical from 1 host
        to N hosts (the reference's DistributedSampler ergonomics).
    global_batch: across all chips; each host feeds global/process_count rows.
    mesh: batches are placed with the mesh's batch-axis sharding; None → plain
        committed host→device transfer (single-device config 1).
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        global_batch: int,
        mesh: Mesh | None = None,
        *,
        shuffle: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        shard_by_host: bool = True,
        partition=None,
        cast_floats=None,
        cast_keys: tuple = ("image",),
    ):
        # The remainder partial batch is always dropped: compiled SPMD steps
        # need static shapes, and a ragged final batch would both recompile
        # and shard unevenly. (The reference's DistributedSampler pads or
        # drops similarly.)
        self.global_batch = global_batch
        self.mesh = mesh
        self.shuffle = shuffle
        self.seed = seed
        self.prefetch = prefetch

        n_proc = jax.process_count()
        if global_batch % n_proc:
            raise ValueError(
                f"global batch {global_batch} not divisible by {n_proc} hosts")
        self.host_batch = global_batch // n_proc
        # Builders that load one shard file per host mark the dataset
        # host_presharded; re-sharding it here would drop (N-1)/N of the data.
        shard_by_host = (shard_by_host
                         and not getattr(dataset, "host_presharded", False))
        if mesh is not None:
            dp = mesh_lib.data_parallel_size(mesh)
            if global_batch % dp:
                raise ValueError(
                    f"global batch {global_batch} not divisible by "
                    f"data-parallel size {dp} (mesh {dict(mesh.shape)})")
        self.dataset = (dataset.shard(n_proc, jax.process_index())
                        if shard_by_host and n_proc > 1 else dataset)
        if len(self.dataset) < self.host_batch:
            raise ValueError(
                f"host shard has {len(self.dataset)} examples < host batch "
                f"{self.host_batch}")
        # ``partition``: PartitionSpec override (seq-parallel configs shard
        # the sequence dim too); trimmed per-leaf to the array rank at
        # device_put so mixed-rank batches work.
        self._partition = partition
        self._sharding = (mesh_lib.batch_sharding(mesh)
                          if mesh is not None else None)
        # ``cast_floats``: the float MODEL-INPUT columns (``cast_keys``,
        # never targets/weights — those feed the loss in f32 and have no
        # compensating device cast) reach the device in this dtype.  The
        # model's first op casts inputs to its compute dtype anyway, so for
        # bf16 configs transferring f32 rows ships 2x the bytes only to
        # round them on arrival.  The rounding is elementwise, so the
        # COLUMN is rounded once, here, on this loader's own copy of the
        # host shard (the caller's data set is never written to), and the
        # worker gathers rows of half the width: same bits as rounding
        # every gathered batch, without 100 ms of ``astype`` a 154 MB
        # batch in the one thread that also gathers and puts.  Cost: the
        # rounded copy stands beside the float column for as long as the
        # caller keeps that one (synthetic ImageNet, 2048 x 224 x 224 x 3:
        # +0.62 GB beside 1.23 GB; ``build_harness`` drops its data sets
        # on return, after which the host holds the copy alone).  A data
        # set that does not fit twice takes ``keep_u8``: 1 byte a pixel,
        # no copy here, normalised on the device.
        self._cast_floats = np.dtype(cast_floats) if cast_floats else None
        self._cast_keys = frozenset(cast_keys)
        self.dataset = self._cast_columns_once(self.dataset)
        # (stop event, thread) of every prefetch worker still alive; see
        # close().  Touched only from the consuming thread.
        self._workers: list[tuple[threading.Event, threading.Thread]] = []

    def steps_per_epoch(self) -> int:
        return len(self.dataset) // self.host_batch

    def _epoch_order(self, epoch: int) -> np.ndarray:
        n = len(self.dataset)
        if not self.shuffle:
            return np.arange(n)
        # Same seed on every host + per-epoch fold-in: hosts draw disjoint
        # shards of one global permutation stream (reference:
        # DistributedSampler.set_epoch).
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, epoch]))
        return rng.permutation(n)

    def epoch(self, epoch: int, *, skip: int = 0) -> Iterator[dict]:
        """Yield device-put batches for one epoch, assembled ``prefetch``
        steps ahead on a background thread (native gather + device_put run
        concurrently with the consumer's compute — the torch DataLoader
        worker role, SURVEY.md §4.1).  ``skip``: drop the first N batches
        without paying device transfer (resume seeking)."""
        order = self._epoch_order(epoch)
        starts = list(range(0, len(order) - self.host_batch + 1,
                            self.host_batch))[skip:]
        q: queue.Queue = queue.Queue(maxsize=max(self.prefetch, 1))
        stop = threading.Event()
        sentinel = object()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            # One gather, cast and put span per batch, then queue_full
            # for as long as the batch is ready and nobody wants it.
            try:
                for n, lo in enumerate(starts, start=skip):
                    idx = order[lo:lo + self.host_batch]
                    with timeline.span("loader.gather", batch=n):
                        rows = self.dataset[idx]
                    item = self._to_device(rows, n)
                    with timeline.span("loader.queue_full", batch=n):
                        wanted = put(item)
                    if not wanted:
                        return  # consumer gone
                    metrics.bump("loader.batches")
                put(sentinel)
            except BaseException as e:  # noqa: BLE001 — surface to consumer
                put(e)

        t = threading.Thread(target=worker, daemon=True,
                             name="tpuframe-prefetch")
        self._workers.append((stop, t))
        t.start()
        try:
            while True:
                with timeline.span("loader.wait"):
                    item = q.get()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # Joined, not just signalled: an abandoned epoch must not
            # leave its worker running ahead inside device_put.
            stop.set()
            t.join()
            self._workers.remove((stop, t))

    def close(self) -> None:
        """Stop and join every prefetch worker this loader started.

        An epoch generator that was never exhausted or closed (the
        training loop's infinite stream) leaves its worker assembling and
        device_put-ing the next batch.  A daemon thread caught inside
        that C++ call when the interpreter finalizes aborts the process
        ("FATAL: exception not rethrown", exit 134) after the run has
        succeeded — so the owner of a loader closes it before returning.
        Bounded by one batch's assembly and transfer."""
        for stop, _ in self._workers:
            stop.set()
        for _, t in self._workers:
            t.join()

    def from_step(self, step: int) -> Iterator[dict]:
        """Infinite stream positioned as if ``step`` batches were already
        consumed — exact-continuation resume (SURVEY.md §5.4 'exact-epoch
        continuation'): the restored run sees the same remaining data order
        as an uninterrupted run."""
        spe = self.steps_per_epoch()
        epoch, offset = divmod(step, spe)
        while True:
            yield from self.epoch(epoch, skip=offset)
            offset = 0
            epoch += 1

    def __iter__(self):
        """Infinite stream across epochs (step-based training loops)."""
        return self.from_step(0)

    def _wants_cast(self, key: str, arr: np.ndarray) -> bool:
        return (self._cast_floats is not None and key in self._cast_keys
                and np.issubdtype(arr.dtype, np.floating)
                and arr.dtype != self._cast_floats)

    def _cast_columns_once(self, dataset: ArrayDataset) -> ArrayDataset:
        """``dataset`` with every column ``_wants_cast`` names rounded to
        ``cast_floats``: new arrays in a new ArrayDataset, the other
        columns shared, the argument untouched (itself, if nothing is to
        cast, or if it is no ArrayDataset and shows no columns: its
        batches are cast as they come).  One ``loader.cast_column`` span a
        column cast."""
        if not isinstance(dataset, ArrayDataset):
            return dataset
        cast = {}
        for key, col in dataset.columns.items():
            if not self._wants_cast(key, col):
                continue
            written = col.size * self._cast_floats.itemsize
            with timeline.span("loader.cast_column", key=key,
                               rows=len(col), bytes=written):
                cast[key] = col.astype(self._cast_floats)
            metrics.bump("loader.bytes_cast_once", written)
        if not cast:
            return dataset
        return dataclasses.replace(dataset,
                                   columns={**dataset.columns, **cast})

    def _to_device(self, rows: dict, n: int) -> dict:
        if self._cast_floats is not None:
            # What ``_cast_columns_once`` left: nothing, unless the data
            # set is no ArrayDataset and could show it no columns.
            with timeline.span("loader.cast", batch=n):
                rows = {k: (v.astype(self._cast_floats)
                            if self._wants_cast(k, v) else v)
                        for k, v in rows.items()}
        # host side only: device_put returns before the transfer ends
        with timeline.span("loader.put", batch=n):
            batch = self._put(rows)
        metrics.bump("loader.bytes_put",
                     sum(v.nbytes for v in jax.tree.leaves(rows)))
        return batch

    def _put(self, batch: dict) -> dict:
        if self._sharding is None:
            return jax.tree.map(jax.device_put, batch)
        # Host rows are this host's slice of the global batch; device_put with
        # a NamedSharding scatters rows to local devices and (multi-host)
        # assembles the logically-global array without gathering.
        def put(x):
            sharding = self._sharding
            if self._partition is not None:
                from jax.sharding import PartitionSpec as P
                sharding = NamedSharding(self.mesh,
                                         P(*self._partition[:x.ndim]))
            return _put_host_shard(x, sharding, self.global_batch)
        return jax.tree.map(put, batch)


def _put_host_shard(x: np.ndarray, sharding: NamedSharding, global_batch: int):
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    global_shape = (global_batch, *x.shape[1:])
    return jax.make_array_from_process_local_data(sharding, x, global_shape)
