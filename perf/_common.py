"""Shared setup for the perf/ scripts: repo-root import path, persistent XLA
compile cache, stderr logging, and chained-async timing."""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup():
    """Import path + the repo's persistent compile cache
    (tpuframe.utils.compile_cache decides where it lives)."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import jax

    from tpuframe.utils import compile_cache

    compile_cache.enable()
    return jax


def make_log(tag: str):
    def log(m):
        print(f"[{tag}] {m}", file=sys.stderr, flush=True)

    return log


def timeit_chain(make_chain, *args, chain: int = 16, reps: int = 3,
                 log=None, min_delta: float = 0.4, max_chain: int = 4096):
    """Per-iteration time of a pure function, dispatch overhead excluded.

    ``make_chain(n)`` must return a jitted function of ``*args`` that runs
    the computation ``n`` times with a data dependence between iterations
    (lax.scan feeding output into input).  Per-iteration cost is
    (t_chainN - t_chain1) / (N - 1), best of ``reps``: dispatch and infeed
    overhead cancel in the difference.

    The chain GROWS (4x steps, up to ``max_chain``) until the measured
    difference clears ``min_delta`` seconds, so a fixed chain that is safe
    for a 20ms program is not host jitter for a 0.2ms one.  Raw chain
    times go to ``log``."""
    import jax

    def best(f):
        jax.block_until_ready(f(*args))  # compile + settle
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return ts

    t_1 = best(make_chain(1))
    n = min(chain, max_chain)  # the caller's memory cap binds from the start
    while True:
        t_n = best(make_chain(n))
        delta = min(t_n) - min(t_1)
        if log is not None:
            log(f"  raw chain{n}: {[round(t * 1e3, 1) for t in t_n]} ms; "
                f"chain1: {[round(t * 1e3, 1) for t in t_1]} ms "
                f"(delta {delta * 1e3:.1f} ms)")
        if delta >= min_delta:
            return delta / (n - 1)
        if n >= max_chain:
            # Refuse to return jitter as data (the failure mode this timer
            # exists to prevent); callers record the error row instead.
            raise RuntimeError(
                f"timeit_chain: delta {delta * 1e3:.1f} ms at chain {n} "
                f"never cleared min_delta {min_delta * 1e3:.0f} ms "
                f"(chain times {[round(t * 1e3, 1) for t in t_n]} ms vs "
                f"chain1 {[round(t * 1e3, 1) for t in t_1]} ms)")
        n = min(n * 4, max_chain)


# ---------------------------------------------------------------------------
# HLO text census (shared by exp_hlo_dump [on-chip] and exp_hlo_offline
# [AOT topology compile] so the two censuses can only disagree for
# compiler reasons, never tooling drift)
# ---------------------------------------------------------------------------

def hlo_shape_census(txt: str):
    """Group HLO tensor mentions by dtype/shape/layout, largest total
    padded bytes first.  TPU layouts look like
    ``bf16[512,112,112,64]{3,2,1,0:T(8,128)(2,1)}``."""
    import re

    shapes = re.findall(r"(bf16|f32|s32|u8|pred)\[([0-9,]*)\]\{([^}]*)\}", txt)
    census: dict = {}
    for dt, dims, layout in shapes:
        key = f"{dt}[{dims}]{{{layout}}}"
        census[key] = census.get(key, 0) + 1
    return sorted(census.items(), key=lambda kv: -hlo_nbytes(kv[0]) * kv[1])


def hlo_nbytes(key: str) -> float:
    """Padded-byte estimate for one census key: the layout's minor dim
    rounds to 128 lanes, the next-minor to 8 sublanes (the (8,128) tile;
    bf16's (2,1) sublane packing does not change the 8-row estimate)."""
    import re

    m = re.match(r"(bf16|f32|s32|u8|pred)\[([0-9,]*)\]\{([^:}]*)", key)
    if not m:
        return 0.0
    dt, dims, perm = m.groups()
    if not dims:
        return 0.0
    sz = {"bf16": 2, "f32": 4, "s32": 4, "u8": 1, "pred": 1}[dt]
    parts = [int(d) for d in dims.split(",") if d]
    if not parts:
        return 0.0
    try:
        mtm = [int(p) for p in perm.split(",") if p.strip() != ""]
    except ValueError:
        mtm = []
    if len(mtm) != len(parts):
        mtm = list(range(len(parts) - 1, -1, -1))
    padded = list(parts)
    if mtm:
        minor = mtm[0]
        padded[minor] = (padded[minor] + 127) // 128 * 128
        if len(mtm) > 1:
            nxt = mtm[1]
            padded[nxt] = (padded[nxt] + 7) // 8 * 8
    n = 1.0
    for d in padded:
        n *= d
    return n * sz


def ensure_cpu_backend():
    """Pin the CPU backend for the offline AOT-census scripts: they
    compile FOR a described TPU and must never claim an attached one.
    Call BEFORE importing jax."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    # Pallas ops auto-interpret when the HOST backend is CPU — but these
    # scripts compile FOR a TPU topology, and an interpret-mode kernel
    # lowers as an XLA while loop, not a Mosaic custom call: the census
    # then measures a program that never runs on chip (discovered
    # round 5 — the first fused-conv-BN census was full of
    # FusedConvBN/while loops, and every round-4 offline "pallas" row
    # has the same defect).  Force real Mosaic lowering.
    os.environ.setdefault("TPUFRAME_PALLAS_INTERPRET", "0")


def to_shape_structs(tree, sharding):
    """Map a pytree of shaped values (arrays or ShapeDtypeStructs, e.g.
    from jax.eval_shape) to sharding-annotated ShapeDtypeStructs for AOT
    lowering against a compile-only topology."""
    import jax

    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
        if hasattr(s, "shape") else s, tree,
        is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))


_AOT_LOCK_HANDLE = None


def _aot_lock_path():
    import os

    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".aot_compile.lock")


def aot_lock(timeout_s: float = 7200.0):
    """Context manager: acquire the machine-wide AOT-compile lock with a
    bounded wait (raises TimeoutError instead of hanging CI forever
    behind a long-running census)."""
    import contextlib
    import fcntl
    import time

    @contextlib.contextmanager
    def _cm():
        fh = open(_aot_lock_path(), "w")
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                try:
                    fcntl.flock(fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"AOT compile lock busy for >{timeout_s}s "
                            f"({_aot_lock_path()}) — another offline "
                            f"census/compile is holding it")
                    time.sleep(5.0)
            yield
        finally:
            fh.close()

    return _cm()


def hold_aot_lock():
    """Serialize compile-only libtpu users machine-wide.

    libtpu guards itself with a /tmp lockfile and ABORTS when a second
    process initializes concurrently (seen 2026-07-31: overlapping AOT
    censuses + the AOT guard tests).  Callers block here until the
    current holder exits; the lock is held for the process lifetime
    (the libtpu conflict window is the whole process, not just init).
    """
    global _AOT_LOCK_HANDLE
    if _AOT_LOCK_HANDLE is not None:
        return
    import fcntl

    fh = open(_aot_lock_path(), "w")
    fcntl.flock(fh, fcntl.LOCK_EX)  # blocks until free
    _AOT_LOCK_HANDLE = fh


def topo_tag_suffix(topo: str, default: str) -> str:
    """Shared result-tag suffix for non-default compile-only topologies
    ("" for the default; "_v4_221"-style otherwise) — one rule for
    exp_hlo_offline / exp_capacity_audit / exp_offline_ab."""
    if topo == default:
        return ""
    return "_" + topo.replace(":", "_").replace("x", "")
