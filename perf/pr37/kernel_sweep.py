"""The three latent flash kernels alone at the cell's shape, ``[1, 8192,
32, 128 + 64 | 128]`` bf16 causal, over tilings and over two layouts of
the 192-wide score: ``split`` (two MXU products, 128 and 64 deep, summed in
f32: what the program runs) and ``concat`` (q and k tiles joined to 192 in
VMEM, one product; patched in here, not an option of the program).  One
JSON line a timing, device time by the host clock around 20 launches.

    chiprun -- python perf/pr37/kernel_sweep.py
"""

import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from tpuframe.ops import flash_attention as fa  # noqa: E402

B, S, N, D, DR, DV = 1, 8192, 32, 128, 64, 128
T = fa.Tiles
SWEEP = {
    "fwd": [None, T(1024, 8192, 1024), T(1024, 8192, 512), T(512, 8192, 512),
            T(2048, 4096, 1024), T(1024, 2048, 1024), T(512, 4096, 1024)],
    "dq": [None, T(1024, 8192, 512), T(512, 4096, 512), T(256, 8192, 512),
           T(512, 8192, 256), T(1024, 4096, 1024)],
    "dkv": [None, T(4096, 1024, 512), T(8192, 1024, 512), T(2048, 512, 512),
            T(8192, 512, 512), T(2048, 1024, 1024), T(4096, 2048, 512)],
}


def concat_qk(q, k, precision, rope=None):
    if rope:
        q = jnp.concatenate([q, rope[0]], axis=1)
        k = jnp.concatenate([k, rope[1]], axis=1)
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    ks = jax.random.split(jax.random.key(0), 6)
    mk = lambda k, n, d: jax.random.normal(  # noqa: E731
        k, (B * n, S, d), jnp.bfloat16)
    q, qr, k, kr, v, do = (mk(ks[0], N, D), mk(ks[1], N, DR), mk(ks[2], N, D),
                           mk(ks[3], 1, DR), mk(ks[4], N, DV),
                           mk(ks[5], N, DV))
    kw = dict(scale=(D + DR) ** -0.5, causal=True, lane=fa._lse_lane_major(),
              interpret=False, group=N)
    rule = dict(zip(fa._KERNELS, fa._tiling(S, S, D, 2, None, None, DR)))
    out, lse = fa._mla_fwd(q, qr, k, kr, v, tiles=rule["fwd"], **kw)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    real_qk = fa._qk
    for layout in ("split", "concat"):
        fa._qk = real_qk if layout == "split" else concat_qk
        jax.clear_caches()
        for kernel, tilings in SWEEP.items():
            for tiles in tilings:
                t = tiles or rule[kernel]
                try:
                    if kernel == "fwd":
                        ms = timed(lambda: fa._mla_fwd(q, qr, k, kr, v,
                                                       tiles=t, **kw))
                    elif kernel == "dq":
                        ms = timed(lambda: fa._mla_bwd_dq(
                            q, qr, k, kr, v, do, lse, delta, tiles=t, **kw))
                    else:
                        ms = timed(lambda: fa._mla_bwd_dkv(
                            q, qr, k, kr, v, do, lse, delta, tiles=t, **kw))
                except Exception as e:  # a tiling Mosaic refuses: say so
                    ms = None
                    print(f"{layout} {kernel} {tuple(t)}: "
                          f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
                print(json.dumps({"layout": layout, "kernel": kernel,
                                  "tiles": list(t), "rule": tiles is None,
                                  "ms": ms}), flush=True)
    # beside them: the plain kernels at one width of 128 (no rope term),
    # the shape of Trinity's full layer with ungrouped heads
    fa._qk = real_qk
    q4 = jax.random.normal(ks[0], (B, S, N, D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: fa.flash_mha(q, k, v, causal=True))
    g = jax.jit(jax.grad(lambda q, k, v: fa.flash_mha(
        q, k, v, causal=True).astype(jnp.float32).sum(), (0, 1, 2)))
    print(json.dumps({"layout": "plain128", "kernel": "fwd",
                      "ms": timed(f, q4, q4, q4)}))
    print(json.dumps({"layout": "plain128", "kernel": "fwd+bwd",
                      "ms": timed(g, q4, q4, q4)}))
    mla = jax.jit(jax.grad(lambda *a: fa.flash_mla(*a).astype(
        jnp.float32).sum(), range(5)))
    unf = lambda x, n: x.reshape(B, n, S, -1).transpose(0, 2, 1, 3)  # noqa: E731
    print(json.dumps({"layout": "split", "kernel": "fwd+bwd (rule, with folds)",
                      "ms": timed(mla, unf(q, N), unf(qr, N), unf(k, N),
                                  unf(kr, 1), unf(v, N))}))


if __name__ == "__main__":
    main()
