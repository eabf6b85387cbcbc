"""Time one layer's decode attention, the kernel against the einsums, on the
attached TPU (PERF.md §6, PR 32: the readings behind
``ops/decode_attention``).

    chiprun -- python perf/decode_attention_sweep.py
    JAX_PLATFORMS=cpu python perf/decode_attention_sweep.py --shape 4,2,64,256

One JSON line per (variant, occupancy): the median over ``--reps``
dispatches of the time of one call (every slot's query against its K and
V ring), from a jitted loop of ``--calls`` dependent calls (each call's
output is the next one's query) between two host clock reads that end in
``block_until_ready``; ``ms_per_step`` is that times the 12 layers of the
124M LM's decode step, ``read_gb_s`` the bytes of the lane blocks at or
below each slot's length, K's and V's, over the time, and
``share_of_819`` that over the v5e's 819 GB/s.  The row to beat is the
einsums': 268 us a ring in the decode program's trace, 570-577 us a layer
in this loop, whatever the slots hold.

Variants: ``einsum`` (``attention._xla_decode_attention``: the stand-in,
what ran before PR 32) and ``mosaic:<block>x<buffers>`` (the kernel with
lane blocks of ``block`` columns and ``buffers`` VMEM buffers; the
module's own choice is ``mosaic``).  Occupancies: every slot at 128, 512
or all columns; ``idle`` (every slot at 1); ``mix``, the serving cell's
own: ``--live`` slots (8 is what ``sched.step``'s ``active`` reads there
since PR 32; 20 what it read at PR 30's step) at log-normal lengths
(median 384, sigma 0.8, capped at the capacity) and the rest idle at 1.
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

LAYERS_PER_STEP = 12
HBM_GB_S = 819.0


def occupancy(name: str, slots: int, cap: int, live: int):
    import numpy as np

    if name == "idle":
        return np.ones(slots, np.int32)
    if name == "full":
        return np.full(slots, cap, np.int32)
    if name == "mix":
        rng = np.random.default_rng(0)
        lengths = np.ones(slots, np.int32)
        at = rng.permutation(slots)[:live]
        lengths[at] = np.clip(rng.lognormal(np.log(384), 0.8, len(at)),
                              17, cap).astype(np.int32)
        return lengths
    return np.full(slots, min(int(name), cap), np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="64,12,64,2048",
                    help="slots, heads, head size, capacity")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--variants", default="einsum,mosaic,mosaic:128x2,"
                    "mosaic:128x8,mosaic:256x4,mosaic:512x3")
    ap.add_argument("--occupancies", default="idle,128,512,full,mix")
    ap.add_argument("--live", type=int, default=8)
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--compile-only", action="store_true",
                    help="compile each variant for a described v5e: no "
                         "chip, no times")
    ap.add_argument("--out", default="chiprun_out/decode_attention_sweep.jsonl")
    args = ap.parse_args(argv)
    if args.compile_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["TPUFRAME_PALLAS_INTERPRET"] = "0"   # lower Mosaic

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpuframe.ops import attention, decode_attention as da, kernel_impl

    slots, heads, head, cap = (int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    shape = (slots, heads, head, cap)
    interpret = kernel_impl.interpret_default()[0]

    def attend(variant):
        if variant == "einsum":
            return attention._xla_decode_attention, None
        block, buffers = da._BLOCK, da._BUFFERS
        if ":" in variant:
            block, buffers = (int(x) for x in
                              variant.split(":")[1].split("x"))
        return functools.partial(da._launch, block=block, buffers=buffers,
                                 interpret=interpret), block

    def loop_of(fn):
        @jax.jit
        def loop(q, k, v, lengths):
            return lax.fori_loop(0, args.calls,
                                 lambda i, q: fn(q, k, v, lengths), q)
        return loop

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        sds = functools.partial(jax.ShapeDtypeStruct, sharding=one)
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            try:
                m = loop_of(attend(variant)[0]).lower(
                    sds((slots, 1, heads, head), dtype), sds(shape, dtype),
                    sds(shape, dtype), sds((slots,), jnp.int32)).compile(
                ).memory_analysis()
                row = {"variant": variant,
                       "compiled_s": time.perf_counter() - t0,
                       "temp_bytes": m.temp_size_in_bytes}
            except Exception as e:  # noqa: BLE001 — a refusal is a reading
                row = {"variant": variant, "error": repr(e)[:2000]}
            print(json.dumps(row), flush=True)
        return 0

    if jax.default_backend() != "tpu":
        print("no TPU: these times are not the chip's", file=sys.stderr)
    dev = jax.devices()[0]
    key = jax.random.key(0)
    q = jax.random.normal(key, (slots, 1, heads, head),
                          jnp.float32).astype(dtype)
    k, v = (jax.random.normal(jax.random.fold_in(key, r), shape,
                              jnp.float32).astype(dtype) for r in (1, 2))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for variant in args.variants.split(","):
            fn, block = attend(variant)
            loop = loop_of(fn)
            for occ in args.occupancies.split(","):
                lengths_np = occupancy(occ, slots, cap, args.live)
                lengths = jnp.asarray(lengths_np)
                row = {"variant": variant, "occupancy": occ,
                       "shape": list(shape), "dtype": dtype.name,
                       "columns": int(lengths_np.sum())}
                try:
                    t0 = time.perf_counter()
                    jax.block_until_ready(loop(q, k, v, lengths))
                    row["first_call_s"] = time.perf_counter() - t0
                    times = []
                    for _ in range(args.reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(loop(q, k, v, lengths))
                        times.append((time.perf_counter() - t0) * 1e3)
                    ms = statistics.median(times) / args.calls
                    got = fn(q, k, v, lengths).astype(jnp.float32)
                    want = attention._xla_decode_attention(
                        q, k, v, lengths).astype(jnp.float32)
                    width = block or cap
                    read = int((-(-lengths_np // width)).sum()) * width \
                        * heads * head * dtype.itemsize * 2
                    row.update(
                        us_per_call=ms * 1e3,
                        ms_per_step=ms * LAYERS_PER_STEP,
                        read_mb=read / 1e6,
                        read_gb_s=read / 1e9 / (ms / 1e3),
                        share_of_819=read / 1e9 / (ms / 1e3) / HBM_GB_S,
                        max_abs_gap=float(jnp.max(jnp.abs(got - want))),
                        reps_ms=times)
                except Exception as e:  # noqa: BLE001 — a variant the
                    # compiler refuses is a reading too; the others run
                    row["error"] = repr(e)[:2000]
                row["device"] = {"platform": dev.platform,
                                 "device_kind": dev.device_kind}
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
