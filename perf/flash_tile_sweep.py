"""Time the three flash-attention kernels, one at a time, over a grid of
tilings on the attached TPU (PERF.md §6, PR 26: the sweep behind
``ops/flash_attention.choose_tiles``).

    chiprun -- python perf/flash_tile_sweep.py --shape 96,2048,64
    python perf/flash_tile_sweep.py --shape 96,2048,64 --compile-only

One JSON line per (kernel, block_q, block_k, sub): the median over
``--reps`` dispatches of the time of one kernel call, from a jitted scan
of ``--calls`` dependent calls between two host clock reads that end in
``block_until_ready``.  ``--compile-only`` compiles each tiling for a
described v5e instead (no chip, no times): what Mosaic refuses there it
refuses on the chip.  ``--e2e`` times ``flash_mha`` forward + backward
whole at the rule's choice and at 128 x 128 instead of the grid.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCKS = (128, 256, 512, 1024, 2048)


def grid(kernel: str, s: int):
    """(block_q, block_k, sub) candidates: the plain blocks of ISSUE 26 (the
    whole block is one score tile) and the walked operand resident with an
    inner loop over sub-blocks."""
    own_blocks = [b for b in BLOCKS[:4] if b <= s]
    out = []
    for own in own_blocks:
        for walked in [b for b in BLOCKS if b <= s]:
            out.append((own, walked, walked))
        for sub in own_blocks:
            if sub < s:
                out.append((own, s, sub))
    seen = set()
    for own, walked, sub in out:
        t = (walked, own, sub) if kernel == "dkv" else (own, walked, sub)
        if t not in seen:
            seen.add(t)
            yield t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="96,2048,64",
                    help="batch*heads, sequence, head size")
    ap.add_argument("--kernels", default="fwd,dq,dkv")
    ap.add_argument("--tiles", default="",
                    help="bq:bk:sub,... instead of the grid")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--e2e", action="store_true")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--out", default="chiprun_out/flash_tile_sweep.jsonl")
    args = ap.parse_args(argv)
    if args.compile_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["TPUFRAME_TUNE_GEN"] = "v5e"   # the chip's row layout
        os.environ["TPUFRAME_TUNE_DB"] = "off"

    import jax
    import jax.numpy as jnp

    from tpuframe.ops import flash_attention as fa

    bn, s, d = (int(x) for x in args.shape.split(","))
    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        print("no TPU attached (use --compile-only here)", file=sys.stderr)
        return 1
    device = jax.devices()[0].device_kind
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    sink = open(args.out, "a")

    def say(row):
        row = dict(shape=[bn, s, d], device=device,
                   compile_only=args.compile_only, **row)
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def measure(fn, shapes):
        """Median seconds of one dispatch of jitted ``fn``."""
        if args.compile_only:
            jax.jit(fn).lower(*[jax.ShapeDtypeStruct(
                sh, dt, sharding=sharding) for sh, dt in shapes]).compile()
            return None
        keys = jax.random.split(jax.random.key(0), len(shapes))
        xs = [jax.random.normal(k_, sh, jnp.float32).astype(dt) * 0.5
              for k_, (sh, dt) in zip(keys, shapes)]
        run = jax.jit(fn)
        jax.block_until_ready(run(*xs))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*xs))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    bf16, f32 = jnp.bfloat16, jnp.float32
    x3, row = ((bn, s, d), bf16), ((bn, s), f32)
    scale = d ** -0.5

    def one_kernel(kernel, tiles):
        kw = dict(scale=scale, causal=True, tiles=tiles, interpret=False,
                  lane=fa._lse_lane_major())

        def fn(q, k, v, do, lse, delta):
            def body(c, _):
                if kernel == "fwd":
                    out, _lse = fa._flash_fwd(c, k, v, None, **kw)
                    return out, None
                if kernel == "dq":
                    return fa._flash_bwd_dq(c, k, v, None, do, lse, delta,
                                            **kw), None
                dk, _dv = fa._flash_bwd_dkv(q, c, v, None, do, lse, delta,
                                            **kw)
                return dk, None
            c, _ = jax.lax.scan(body, k if kernel == "dkv" else q, None,
                                length=args.calls)
            return c
        return fn

    if args.e2e:
        b = bn // args.heads
        x4 = ((b, s, args.heads, d), bf16)
        for name, blocks in (("rule", {}),
                             ("128x128", dict(block_q=128, block_k=128))):
            def fn(q, k, v, blocks=blocks):
                def loss(q, k, v):
                    return fa.flash_mha(q, k, v, causal=True, interpret=False,
                                        **blocks).astype(f32).sum()
                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            try:
                t = measure(fn, [x4, x4, x4])
                say(dict(e2e=name, ms=None if t is None else t * 1e3,
                         tiling=[list(x) for x in fa._tiling(
                             s, s, d, 2, blocks.get("block_q"),
                             blocks.get("block_k"))]))
            except Exception as e:  # noqa: BLE001 — a sweep records refusals
                say(dict(e2e=name, error=f"{type(e).__name__}: {e}"[:300]))
        return 0

    for kernel in args.kernels.split(","):
        cands = ([tuple(int(x) for x in t.split(":"))
                  for t in args.tiles.split(",")] if args.tiles
                 else list(grid(kernel, s)))
        for bq, bk, sub in cands:
            tiles = fa.Tiles(bq, bk, sub)
            rec = dict(kernel=kernel, block_q=bq, block_k=bk, sub=sub,
                       vmem_est=fa.vmem_bytes(kernel, tiles, d, 2))
            try:
                t = measure(one_kernel(kernel, tiles),
                            [x3, x3, x3, x3, row, row])
                rec["ms"] = None if t is None else t / args.calls * 1e3
            except Exception as e:  # noqa: BLE001 — a sweep records refusals
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            say(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
