"""Time one decode step's KV store, the ways it can be written, on the
attached TPU (PERF.md §6, PR 30: the readings behind ``ops/ring_store``).

    chiprun -- python perf/ring_store_sweep.py
    JAX_PLATFORMS=cpu python perf/ring_store_sweep.py --shape 4,4,16,256

One JSON line per variant: the median over ``--reps`` dispatches of the
time of one store (every slot's new row into one ring), from a jitted
loop of ``--calls`` dependent stores on a donated ring between two host
clock reads that end in ``block_until_ready``; ``ms_per_step`` is that
times the 24 stores of the 124M LM's decode step.  Variants:

  parent        rings of [slots, capacity, heads, head], the store as
                ``vmap(dynamic_update_slice)``: what ran before PR 30
  standin       the same composition on [slots, heads, head, capacity]
                (``ring_store._xla_store``)
  mosaic        ``ring_store.ring_store``: the kernel
  mosaic_pair   the kernel with K's and V's ring in one call (timed per
                ring, so it compares with ``mosaic``)
  mosaic_dma    the kernel's select, the blocks moved by DMAs of its
                own: one invocation, groups of ``--group`` slots in
                flight, the next group's reads under this group's writes
  unrolled      one ``dynamic_update_slice`` per slot, unrolled
  onehot        a select over the whole ring
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STORES_PER_STEP = 24   # 12 layers x (K, V)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="64,12,64,2048",
                    help="slots, heads, head size, capacity")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--variants", default="parent,standin,mosaic,"
                    "mosaic_pair,mosaic_dma,unrolled,onehot")
    ap.add_argument("--group", type=int, default=8)
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--compile-only", action="store_true",
                    help="compile each variant for a described v5e: no "
                         "chip, no times")
    ap.add_argument("--out", default="chiprun_out/ring_store_sweep.jsonl")
    args = ap.parse_args(argv)
    if args.compile_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["TPUFRAME_PALLAS_INTERPRET"] = "0"   # lower Mosaic

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpuframe.ops import kernel_impl, ring_store as rs

    slots, heads, head, cap = (int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    shape = (slots, heads, head, cap)
    interpret = kernel_impl.interpret_default()[0]

    def pair_store(rings, rows, idx):
        """ring_store's kernel over two rings at once."""
        row, zeros = shape[1:-1], (0, 0)

        def kernel(idx_ref, ka, kb, ra, rb, oa, ob):
            for cols_ref, ring_ref, out_ref in ((ka, ra, oa), (kb, rb, ob)):
                rs._store_kernel(idx_ref, cols_ref, ring_ref, out_ref)

        cols = [rs._lane_columns(r) for r in rows]
        ring_spec = pl.BlockSpec((1,) + row + (128,),
                                 lambda s, i: (s,) + zeros + (i[s] // 128,))
        cols_spec = pl.BlockSpec(row + (128,),
                                 lambda s, i: zeros + (s // 128,))
        sds = jax.ShapeDtypeStruct(shape, dtype)
        return pl.pallas_call(
            kernel, name="ring_store_pair",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(slots,),
                in_specs=[cols_spec, cols_spec, ring_spec, ring_spec],
                out_specs=[ring_spec, ring_spec]),
            out_shape=[sds, sds], input_output_aliases={3: 0, 4: 1},
            interpret=interpret)(idx, *cols, *rings)

    def dma_store(ring, rows, idx):
        """The select of ring_store's kernel; the lane blocks travel by
        DMAs the kernel starts itself, a group of slots at a time."""
        row, group = shape[1:-1], args.group
        n_groups = slots // group
        assert slots % group == 0

        def kernel(idx_ref, cols_ref, ring_ref, out_ref, buf, sem_in,
                   sem_out):
            def block(ref, s):
                start = pl.multiple_of(idx_ref[s] // 128 * 128, 128)
                return ref.at[(s,) + (slice(None),) * len(row)
                              + (pl.ds(start, 128),)]

            def reads(g):
                return [pltpu.make_async_copy(
                    block(ring_ref, g * group + j), buf.at[g % 2, j],
                    sem_in.at[g % 2, j]) for j in range(group)]

            def writes(g):
                return [pltpu.make_async_copy(
                    buf.at[g % 2, j], block(out_ref, g * group + j),
                    sem_out.at[g % 2, j]) for j in range(group)]

            for copy in reads(0):
                copy.start()
            for g in range(n_groups):
                if g + 1 < n_groups:
                    if g >= 1:   # the buffer the next reads land in
                        for copy in writes(g - 1):
                            copy.wait()
                    for copy in reads(g + 1):
                        copy.start()
                for j, copy in enumerate(reads(g)):
                    copy.wait()
                    s = g * group + j
                    at = s // 128 * 128
                    buf[g % 2, j] = rs._with_column(
                        buf[g % 2, j], cols_ref[..., at:at + 128],
                        lax.rem(idx_ref[s], 128), s % 128)
                for copy in writes(g):
                    copy.start()
            for g in range(max(n_groups - 2, 0), n_groups):
                for copy in writes(g):
                    copy.wait()

        cols = rs._lane_columns(rows)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            kernel, name="ring_store_dma",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(cols.shape, lambda i, idx: (0, 0, 0)),
                          any_spec],
                out_specs=any_spec,
                scratch_shapes=[
                    pltpu.VMEM((2, group) + row + (128,), dtype),
                    pltpu.SemaphoreType.DMA((2, group)),
                    pltpu.SemaphoreType.DMA((2, group))]),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            input_output_aliases={2: 0},
            interpret=interpret)(idx, cols, ring)

    def unrolled(ring, rows, idx):
        for s in range(slots):
            ring = lax.dynamic_update_slice(
                ring, rows[s][None, ..., None], (s, 0, 0, idx[s]))
        return ring

    def onehot(ring, rows, idx):
        lanes = lax.broadcasted_iota(jnp.int32, ring.shape, 3)
        return jnp.where(lanes == idx[:, None, None, None],
                         rows[..., None], ring)

    def parent(ring, rows, idx):   # ring [slots, capacity, heads, head]
        return jax.vmap(lambda c, r, i: lax.dynamic_update_slice(
            c, r[None], (i, 0, 0)))(ring, rows, idx)

    variants = {
        "parent": (parent, 1, (slots, cap, heads, head)),
        "standin": (rs._xla_store, 1, shape),
        "mosaic": (rs.ring_store, 1, shape),
        "mosaic_pair": (pair_store, 2, shape),
        "mosaic_dma": (dma_store, 1, shape),
        "unrolled": (unrolled, 1, shape),
        "onehot": (onehot, 1, shape),
    }

    def timed(name):
        store, n_rings, ring_shape = variants[name]

        @functools.partial(jax.jit, donate_argnums=0)
        def loop(rings, rows, idx):
            def body(i, rings):
                at = lax.rem(idx + i, cap)
                new = rows + i.astype(rows.dtype)
                if n_rings == 1:
                    return (store(rings[0], new, at),)
                return tuple(store(rings, (new,) * n_rings, at))
            return lax.fori_loop(0, args.calls // n_rings, body, rings)

        if args.compile_only:
            from jax.experimental import topologies
            from jax.sharding import SingleDeviceSharding

            one = SingleDeviceSharding(topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices[0])
            sds = functools.partial(jax.ShapeDtypeStruct, sharding=one)
            t0 = time.perf_counter()
            m = loop.lower((sds(ring_shape, dtype),) * n_rings,
                           sds(shape[:-1], dtype),
                           sds((slots,), jnp.int32)).compile(
            ).memory_analysis()
            return {"variant": name, "compiled_s": time.perf_counter() - t0,
                    "alias_bytes": m.alias_size_in_bytes,
                    "temp_bytes": m.temp_size_in_bytes}

        key = jax.random.key(0)
        rings = tuple(jax.random.normal(jax.random.fold_in(key, r),
                                        ring_shape, jnp.float32).astype(dtype)
                      for r in range(n_rings))
        rows = jax.random.normal(key, shape[:-1], jnp.float32).astype(dtype)
        idx = (jnp.arange(slots, dtype=jnp.int32) * 37 + 5) % cap
        rings0 = rings[0] if name != "parent" else jnp.moveaxis(
            rings[0], 1, -1)
        rings0 = rings0 + 0   # a copy the donation leaves alone
        t0 = time.perf_counter()
        rings = jax.block_until_ready(loop(rings, rows, idx))   # compiles
        compile_s = time.perf_counter() - t0
        rings1 = rings[0] + 0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            rings = jax.block_until_ready(loop(rings, rows, idx))
            times.append((time.perf_counter() - t0) * 1e3)
        stores = args.calls // n_rings * n_rings
        ms = statistics.median(times) / stores
        # the stores of the loop, once more by the stand-in: same bits
        want = rings0
        for i in range(args.calls // n_rings):
            want = rs._xla_store(want, rows + jnp.asarray(i, dtype),
                                 (idx + i) % cap)
        got = rings1 if name != "parent" else jnp.moveaxis(rings1, 1, -1)
        return {"variant": name, "shape": list(ring_shape),
                "dtype": dtype.name, "stores": stores,
                "equals_standin": bool((got == want).all()),
                "ms_per_store": ms, "ms_per_step": ms * STORES_PER_STEP,
                "first_call_s": compile_s, "reps_ms": times}

    if not args.compile_only and jax.default_backend() != "tpu":
        print("no TPU: these times are not the chip's", file=sys.stderr)
    dev = jax.devices()[0]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for name in args.variants.split(","):
            try:
                row = timed(name)
            except Exception as e:  # noqa: BLE001 — a variant the compiler
                # refuses is a reading too; the others still run
                row = {"variant": name, "error": repr(e)[:2000]}
            row["device"] = {"platform": dev.platform,
                             "device_kind": dev.device_kind}
            line = json.dumps(row)
            print(line, flush=True)
            out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
