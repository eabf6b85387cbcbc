"""Pallas flash attention vs the XLA einsum path on the real chip.

VERDICT r2 #2's measurement half: tokens/s fwd and fwd+bwd at seq 2k-8k,
causal, bf16 — the long-context shape class.  Results go into BASELINE.md.

Each measurement jits a chain of ``n`` attention calls whose output feeds
the next call's query, and the per-call time is (t(n=N) - t(n=1)) / (N-1):
dispatch-overhead-free, still one HBM-resident loop (perf/_common.py).

    python perf/bench_attention.py            # all seqs, both impls
    SEQS=2048 python perf/bench_attention.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import make_log, setup, timeit_chain

jax = setup()
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpuframe.ops import attention as attn_ops
from tpuframe.ops.flash_attention import flash_mha

SEQS = [int(s) for s in os.environ.get("SEQS", "2048,4096,8192").split(",")]
HEADS = int(os.environ.get("HEADS", "8"))
HEAD_DIM = int(os.environ.get("HEAD_DIM", "64"))
BATCH = int(os.environ.get("B", "4"))
# Starting chain length; timeit_chain grows it until the timing difference
# clears host jitter (perf/_common.py).
CHAIN = int(os.environ.get("N", "32"))

log = make_log("attn-bench")


def fwd_chain(f, n):
    """jit of n chained attention calls: out_i becomes query_{i+1}."""
    def g(q, k, v):
        def body(x, _):
            return f(x, k, v).astype(q.dtype), None
        x, _ = lax.scan(body, q, None, length=n)
        return x
    return jax.jit(g)


def fwdbwd_chain(f, n):
    """jit of grad-through-n-chained-calls: n forwards + n backwards."""
    def loss(q, k, v):
        def body(x, _):
            return f(x, k, v).astype(q.dtype), None
        x, _ = lax.scan(body, q, None, length=n)
        return jnp.sum(x.astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def main():
    log(f"backend={jax.default_backend()} b={BATCH} h={HEADS} d={HEAD_DIM} "
        f"chain={CHAIN}")
    rows = []
    for s in SEQS:
        rng = np.random.default_rng(0)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.normal(0, 0.5, size=(BATCH, s, HEADS, HEAD_DIM)), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        tokens = BATCH * s

        impls = {
            "pallas": lambda q, k, v: flash_mha(
                q, k, v, causal=True, interpret=False),
            "xla": lambda q, k, v: attn_ops.multihead_attention(
                q, k, v, causal=True, impl="xla"),
        }
        # The materialized [B,H,S,S] f32 scores of the xla path: don't even
        # try shapes that cannot fit in HBM.
        score_gb = BATCH * HEADS * s * s * 4 / 1e9
        if score_gb > 4:
            rows.append({"seq": s, "impl": "xla",
                         "error": f"skipped: S^2 scores ~{score_gb:.0f}GB "
                                  f"exceed HBM (flash runs this shape)"})
            log(str(rows[-1]))
            impls.pop("xla")
        # grad-of-scan saves per-iteration residuals (~4 tensors of
        # b*s*h*d bf16 each); cap the bwd chain so they fit in ~4 GB of
        # HBM rather than letting the adaptive growth OOM the chip.
        resid_bytes = 4 * BATCH * s * HEADS * HEAD_DIM * 2
        max_bwd_chain = max(8, int(4e9 / resid_bytes))
        for name, f in impls.items():
            try:
                t_f = timeit_chain(
                    lambda n: fwd_chain(f, n), q, k, v, chain=CHAIN, log=log)
                t_fb = timeit_chain(
                    lambda n: fwdbwd_chain(f, n), q, k, v, chain=CHAIN,
                    log=log, max_chain=max_bwd_chain, min_delta=0.25)
                row = {"seq": s, "impl": name,
                       "fwd_ms": round(t_f * 1e3, 3),
                       "fwd_tokens_per_s": round(tokens / t_f),
                       "fwdbwd_ms": round(t_fb * 1e3, 3),
                       "fwdbwd_tokens_per_s": round(tokens / t_fb)}
            except Exception as e:  # noqa: BLE001 — record and continue
                row = {"seq": s, "impl": name,
                       "error": f"{type(e).__name__}: {e}"[:300]}
            rows.append(row)
            log(str(row))
    import json
    print(json.dumps(rows, indent=1), flush=True)


if __name__ == "__main__":
    main()
