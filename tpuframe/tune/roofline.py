"""Roofline scorer: compiled-program cost/memory analysis -> predicted step
time lower bound, binding-resource verdict, and a fits/OOM check.

Per-generation hardware tables — the one peak table of the repo (bench.py,
obs.goodput and the tuner all read it).  The v5e numbers are the ones every
PERF.md roofline uses (197 TFLOPs bf16, 0.81 TB/s HBM, 15.75 GB usable HBM).
:func:`device_generation` maps the attached device's ``device_kind`` to a
row of it.

Honesty caveats carried from PERF.md:

  - §8: XLA's ``cost_analysis`` counts a ``lax.scan`` body ONCE per program
    (not once per iteration) and cannot see inside a pallas custom call, so
    flop/byte totals of scan-containing programs are LOWER BOUNDS.  Scores
    for such programs are tagged ``bytes_lower_bound=True``; temp/argument
    memory and the fits verdict are exact either way.
  - §7.4a: the roofline is a LOWER bound on step time — the measured
    ResNet-50 step sits at ~81% of the HBM roofline (scheduling gap), so a
    predicted 177 ms means "not faster than 177 ms", never "177 ms".

Pure stdlib at import (``device_generation`` imports jax when called).
``score_compiled`` takes the compiled object duck-typed (anything with
``cost_analysis``/``memory_analysis``/``as_text``).
"""

from __future__ import annotations

import dataclasses

from tpuframe.tune import db

GiB = 1024 ** 3


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Per-chip peaks for one TPU generation."""

    generation: str
    bf16_flops: float      # peak bf16 FLOPs/s (MXU)
    hbm_bytes_per_s: float  # peak HBM bandwidth, bytes/s
    hbm_capacity_bytes: float  # usable HBM per chip, bytes
    ici_bytes_per_s: float = 0.0  # aggregate ICI bandwidth per chip, bytes/s
    dcn_bytes_per_s: float = 0.0  # per-chip DCN share for cross-slice hops


# Sources: v5e column = PERF.md §2 (197e12 / 0.81e12 / 15.75 GB, the values
# every recorded roofline in this repo was computed against).  Peak-flops
# column for v4/v5p/v6e = public TPU spec sheets.  v4 HBM = 1.23 TB/s /
# 32 GB, v5p = 2.76 TB/s / 95 GB, v6e = 1.64 TB/s / 32 GB (public TPU
# system specs; only the v5e row is pinned by recorded measurements here).
# ICI column: aggregate interchip bandwidth per chip from the same public
# specs — v4 2400 Gbps, v5e 1600 Gbps, v5p 4800 Gbps, v6e 3584 Gbps.
# DCN column: a cross-slice collective leaves the ICI torus through the
# hosts' datacenter NICs — modeled as one 200 Gbps NIC shared by a
# 4-chip host, i.e. 6.25 GB/s per chip, for every generation.  That is
# an ASSUMPTION (no multislice measurement exists in this repo yet —
# PERF.md §23); the check_tables DCN anchor pins it so it cannot move
# silently, and the 32x ICI:DCN ratio on v5e is the whole reason the
# slice axis must carry the lightest collectives.
HARDWARE = {
    "v4": Hardware("v4", 275e12, 1.23e12, 32.0 * 1e9, 300e9, 6.25e9),
    "v5e": Hardware("v5e", 197e12, 0.81e12, 15.75 * 1e9, 200e9, 6.25e9),
    "v5p": Hardware("v5p", 459e12, 2.76e12, 95.0 * 1e9, 600e9, 6.25e9),
    "v6e": Hardware("v6e", 918e12, 1.64e12, 32.0 * 1e9, 448e9, 6.25e9),
}


# ``jax.devices()[0].device_kind`` -> HARDWARE key.  A TPU whose kind is
# not listed is an error (device_generation), never a default.
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
}

# What a CPU run prices MFU/HBM rows against (the event-log tests run
# there); always labelled ``assumed``, never reported as a device fact.
ASSUMED_GENERATION = "v5e"


def device_generation(device=None) -> tuple[str, str]:
    """``(generation, source)`` for the device a program runs on.

    ``source`` says where the generation came from: ``env`` —
    ``TPUFRAME_TUNE_GEN``, the explicit override (the compile-only tools
    target a chip that is described, not attached); ``device`` — the
    attached TPU's ``device_kind`` looked up in :data:`DEVICE_KINDS`;
    ``assumed`` — no TPU attached (CPU runs), :data:`ASSUMED_GENERATION`.
    A TPU whose kind is not in the table raises: its peaks are unknown,
    and a default would price it as another chip."""
    env = db.target_generation()  # the one reader of TPUFRAME_TUNE_GEN
    if env:
        return env, "env"
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return ASSUMED_GENERATION, "assumed"
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(f"unknown TPU device_kind {kind!r}; have "
                       f"{sorted(DEVICE_KINDS)} — add it to "
                       f"tune/roofline.py with its peaks, or set "
                       f"TPUFRAME_TUNE_GEN")
    return DEVICE_KINDS[kind], "device"


def generation_from_topology(topology: str) -> str:
    """'v5e:2x2' -> 'v5e' (the topology-string prefix jax's
    ``get_topology_desc`` accepts)."""
    return topology.split(":", 1)[0].strip().lower()


def n_chips_from_topology(topology: str) -> int:
    """'v5e:2x2' -> 4, without initializing a compile-only backend."""
    _, _, dims = topology.partition(":")
    n = 1
    for d in dims.split("x"):
        n *= int(d)
    return n


def get_hardware(generation: str) -> Hardware:
    gen = generation.split(":", 1)[0].strip().lower()
    if gen not in HARDWARE:
        raise KeyError(f"unknown TPU generation {generation!r}; "
                       f"have {sorted(HARDWARE)}")
    return HARDWARE[gen]


def score(generation: str, *, flops: float, bytes_accessed: float,
          peak_memory_bytes: float | None = None,
          contains_scan: bool = False) -> dict:
    """Roofline score for one compiled program on one chip generation.

    Returns a JSON-able dict:
      t_mxu_ms / t_hbm_ms — compute and bandwidth rooflines
      predicted_ms        — max of the two (the binding one); a LOWER bound
      bound               — "mxu" | "hbm" (which roofline binds)
      fits                — peak_memory_bytes <= HBM capacity (None if the
                            caller didn't supply memory)
      bytes_lower_bound   — §8 scan caveat: totals undercount, so
                            predicted_ms is even more of a lower bound
    """
    hw = get_hardware(generation)
    t_mxu_ms = flops / hw.bf16_flops * 1e3
    t_hbm_ms = bytes_accessed / hw.hbm_bytes_per_s * 1e3
    fits = None
    if peak_memory_bytes is not None:
        fits = peak_memory_bytes <= hw.hbm_capacity_bytes
    return {
        "generation": hw.generation,
        "flops": flops,
        "bytes": bytes_accessed,
        "t_mxu_ms": round(t_mxu_ms, 2),
        "t_hbm_ms": round(t_hbm_ms, 2),
        "predicted_ms": round(max(t_mxu_ms, t_hbm_ms), 2),
        "bound": "hbm" if t_hbm_ms >= t_mxu_ms else "mxu",
        "fits": fits,
        "peak_memory_bytes": peak_memory_bytes,
        "bytes_lower_bound": bool(contains_scan),
    }


@dataclasses.dataclass(frozen=True)
class DecodeScore:
    """Roofline upper bound on serving decode throughput for one chip."""

    generation: str
    bytes_params: float        # weights read once per step
    bytes_kv: float            # KV cache read (+ the step's writes)
    bytes_per_step: float
    flops_per_step: float
    t_step_ms: float           # lower bound on one decode step
    bound: str                 # "hbm" | "mxu"
    tokens_per_s: float        # slots / t_step — one chip, upper bound
    tokens_per_s_per_chip: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def decode_score(*, param_bytes: float, kv_bytes_per_token: float,
                 slots: int, context: int, generation: str = "v5e",
                 param_dtype_bytes: int = 4) -> DecodeScore:
    """Analytic tokens/sec UPPER bound for the batched decode step.

    Decode at query length 1 is memory-bound on every current TPU: the
    step must stream every weight byte once (batch amortizes it across
    ``slots`` tokens but not below one full read) plus each slot's live
    KV window (``context`` cached tokens at ``kv_bytes_per_token`` =
    ``CacheSpec.bytes_per_token()``, all layers, K+V) and write this
    step's new KV entry.  FLOPs are the weight matmuls (2 * params per
    token); attention FLOPs at query length 1 are negligible beside
    them, keeping the bound honest (lower t, higher tokens/sec).

    One chip, replica-local (the ``serve-dp-decode`` audit proves plain
    DP serving adds no collective time) — so the per-chip number IS the
    chip number, and fleet throughput scales linearly until the
    scheduler runs out of requests.
    """
    if slots < 1 or context < 0:
        raise ValueError(f"need slots >= 1, context >= 0; "
                         f"got {slots}, {context}")
    hw = get_hardware(generation)
    bytes_kv = float(slots * (context + 1) * kv_bytes_per_token)
    bytes_per_step = float(param_bytes) + bytes_kv
    flops = 2.0 * (float(param_bytes) / param_dtype_bytes) * slots
    t_hbm_ms = bytes_per_step / hw.hbm_bytes_per_s * 1e3
    t_mxu_ms = flops / hw.bf16_flops * 1e3
    t_step_ms = max(t_hbm_ms, t_mxu_ms)
    tokens_per_s = slots / (t_step_ms / 1e3) if t_step_ms > 0 else 0.0
    return DecodeScore(
        generation=hw.generation,
        bytes_params=float(param_bytes),
        bytes_kv=bytes_kv,
        bytes_per_step=bytes_per_step,
        flops_per_step=flops,
        t_step_ms=round(t_step_ms, 4),
        bound="hbm" if t_hbm_ms >= t_mxu_ms else "mxu",
        tokens_per_s=round(tokens_per_s, 2),
        tokens_per_s_per_chip=round(tokens_per_s, 2),
    )


# Ring-algorithm wire multipliers on (n-1)/n * bytes: an all-reduce moves
# every byte twice (reduce-scatter phase + all-gather phase); the one-phase
# collectives move it once.  collective-permute is a single neighbor hop.
_COMM_RING_FACTORS = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def comm_ms(generation: str, kind: str, nbytes: float,
            n_devices: int) -> float:
    """Predicted ICI milliseconds for one collective: ring model,
    ``factor * (n-1)/n * bytes / ici_bw``.  ``nbytes`` must be the op's
    bytes as PARSED FROM THE COMPILED HLO (``hlo_audit``'s ruler — a
    bf16 payload counts 2 bytes/element), never re-derived from the
    program's accumulation dtype."""
    hw = get_hardware(generation)
    if hw.ici_bytes_per_s <= 0 or n_devices <= 1:
        return 0.0
    factor = _COMM_RING_FACTORS.get(kind, 1.0)
    scale = (n_devices - 1) / n_devices
    return factor * scale * float(nbytes) / hw.ici_bytes_per_s * 1e3


def dcn_ms(generation: str, kind: str, nbytes: float,
           n_slices: int) -> float:
    """Predicted DCN milliseconds for one cross-slice collective: the
    same ring model as :func:`comm_ms` but over the slice count and the
    per-chip DCN share — ``factor * (s-1)/s * bytes / dcn_bw``.  Like
    the ICI model, ``nbytes`` is the op's bytes as parsed from the
    compiled HLO."""
    hw = get_hardware(generation)
    if hw.dcn_bytes_per_s <= 0 or n_slices <= 1:
        return 0.0
    factor = _COMM_RING_FACTORS.get(kind, 1.0)
    scale = (n_slices - 1) / n_slices
    return factor * scale * float(nbytes) / hw.dcn_bytes_per_s * 1e3


def hbm_ms(generation: str, nbytes: float) -> float:
    """Predicted HBM milliseconds to stream ``nbytes`` on one chip — the
    same bandwidth roofline as :func:`score`'s ``t_hbm_ms``, exposed per
    byte count so the schedule auditor can price interleavable compute
    (compute ops are overwhelmingly bandwidth-bound at audit scale, so
    the byte roofline is the honest lower bound on how long they give a
    scheduler to hide a collective behind)."""
    hw = get_hardware(generation)
    return float(nbytes) / hw.hbm_bytes_per_s * 1e3


def comm_score(generation: str, report, n_devices: int) -> dict:
    """Per-kind predicted comm rows for one program's collectives.

    ``report`` is an ``hlo_audit.CollectiveReport`` (or anything with
    ``bytes_by_kind()``).  Wire-dtype awareness comes from the report
    itself: its byte totals were counted off the optimized HLO's result
    shapes.  ``t_ici_ms`` totals
    are a LOWER bound (assumes zero overlap loss, full ring bandwidth).
    """
    by_kind = report.bytes_by_kind()
    rows = [
        {"kind": k, "bytes": int(b),
         "t_ici_ms": round(comm_ms(generation, k, b, n_devices), 4)}
        for k, b in sorted(by_kind.items())
    ]
    return {
        "generation": get_hardware(generation).generation,
        "n_devices": int(n_devices),
        "rows": rows,
        "comm_bytes": int(sum(r["bytes"] for r in rows)),
        "t_ici_ms": round(sum(r["t_ici_ms"] for r in rows), 4),
    }


def comm_split_score(generation: str, split: dict, *, n_devices: int,
                     n_slices: int) -> dict:
    """Per-kind predicted comm rows with the wire attributed to its
    fabric: ``split`` is shardflow's ICI/DCN byte attribution
    (``{"ici": {kind: bytes}, "dcn": {kind: bytes}}`` — a collective
    whose replica groups span slices is charged to DCN).  ICI rows are
    priced over the full device ring, DCN rows over the slice ring and
    the per-chip DCN share; on v5e the ~32x bandwidth gap between the
    two columns is the multi-slice placement signal."""
    rows = []
    for fabric, priced in (("ici", lambda k, b: comm_ms(
            generation, k, b, n_devices)),
                           ("dcn", lambda k, b: dcn_ms(
            generation, k, b, n_slices))):
        for kind, nbytes in sorted((split.get(fabric) or {}).items()):
            rows.append({"fabric": fabric, "kind": kind,
                         "bytes": int(nbytes),
                         "t_ms": round(priced(kind, nbytes), 4)})
    ici_ms = sum(r["t_ms"] for r in rows if r["fabric"] == "ici")
    dcn_ms_total = sum(r["t_ms"] for r in rows if r["fabric"] == "dcn")
    return {
        "generation": get_hardware(generation).generation,
        "n_devices": int(n_devices),
        "n_slices": int(n_slices),
        "rows": rows,
        "ici_bytes": int(sum(r["bytes"] for r in rows
                             if r["fabric"] == "ici")),
        "dcn_bytes": int(sum(r["bytes"] for r in rows
                             if r["fabric"] == "dcn")),
        "t_ici_ms": round(ici_ms, 4),
        "t_dcn_ms": round(dcn_ms_total, 4),
    }


def contains_scan(hlo_text: str) -> bool:
    """§8 detector: a lowered-to-TPU ``lax.scan`` shows up as an HLO while
    loop.  (Interpret-mode pallas also lowers as a while loop — one more
    reason the sweep forces real Mosaic lowering.)"""
    return "while(" in hlo_text or " while " in hlo_text


def score_compiled(compiled, generation: str) -> dict:
    """Score a jax AOT ``compiled`` object (``.lower(...).compile()``).

    Duck-typed so this module needs no jax import.  Any missing analysis
    (some backends return None) degrades to zeros rather than raising —
    the search driver records the row either way.
    """
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:  # noqa: BLE001 — cost_analysis is best-effort too
        ca = {}
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes)
    except Exception:  # noqa: BLE001 — memory_analysis is best-effort
        pass
    try:
        scan = contains_scan(compiled.as_text())
    except Exception:  # noqa: BLE001
        scan = False
    return score(generation, flops=flops, bytes_accessed=nbytes,
                 peak_memory_bytes=peak, contains_scan=scan)


def check_tables() -> list:
    """Sanity checks for the CI gate (analysis `_run_tune_check`): every
    generation has positive peaks, and the v5e row reproduces PERF.md §2's
    recorded ResNet-50 b=512 anchors (1.252e13 flops / 1.435e11 bytes ->
    63.6 ms MXU, 177 ms HBM, bandwidth-bound).  Returns a list of problem
    strings; empty means healthy."""
    problems = []
    for gen, hw in sorted(HARDWARE.items()):
        if not (hw.bf16_flops > 0 and hw.hbm_bytes_per_s > 0
                and hw.hbm_capacity_bytes > 0):
            problems.append(f"hardware table {gen}: non-positive peak")
        if hw.bf16_flops / hw.hbm_bytes_per_s > 1000:
            problems.append(f"hardware table {gen}: arithmetic intensity "
                            f"ridge >1000 flops/byte — units wrong?")
    s = score("v5e", flops=1.252e13, bytes_accessed=1.435e11)
    if abs(s["t_mxu_ms"] - 63.6) > 0.5:
        problems.append(f"v5e MXU anchor drifted: {s['t_mxu_ms']} != 63.6 ms")
    if abs(s["t_hbm_ms"] - 177.2) > 0.5:
        problems.append(f"v5e HBM anchor drifted: {s['t_hbm_ms']} != 177.2 ms")
    if s["bound"] != "hbm":
        problems.append("v5e ResNet-50 anchor must be bandwidth-bound")
    for gen, hw in sorted(HARDWARE.items()):
        if not hw.ici_bytes_per_s > 0:
            problems.append(f"hardware table {gen}: non-positive ICI peak")
    # Comm-model anchor: ResNet-50's 102.23 MB f32 grad all-reduce on a
    # v5e 2x2 ring is 2 * 3/4 * 1.0223e8 / 200e9 = 0.767 ms.
    t_f32 = comm_ms("v5e", "all-reduce", 1.0223e8, 4)
    if abs(t_f32 - 0.767) > 0.005:
        problems.append(f"v5e comm anchor drifted: {t_f32:.4f} != 0.767 ms")
    # DCN anchor (mirrors the ICI one): the same 102.23 MB grad
    # all-reduce crossing 2 slices is 2 * 1/2 * 1.0223e8 / 6.25e9 =
    # 16.357 ms — ~21x the 4-chip ICI ring, which is the whole point of
    # attributing the split.  Linearity in bytes is pinned too, so the
    # dcn_bytes_per_s table cannot silently regress shape.
    for gen, hw in sorted(HARDWARE.items()):
        if not hw.dcn_bytes_per_s > 0:
            problems.append(f"hardware table {gen}: non-positive DCN peak")
        elif hw.dcn_bytes_per_s >= hw.ici_bytes_per_s:
            problems.append(f"hardware table {gen}: DCN share >= ICI peak "
                            f"— the fabrics are swapped")
    t_dcn = dcn_ms("v5e", "all-reduce", 1.0223e8, 2)
    if abs(t_dcn - 16.357) > 0.01:
        problems.append(f"v5e DCN anchor drifted: {t_dcn:.4f} != 16.357 ms")
    if abs(dcn_ms("v5e", "all-reduce", 2 * 1.0223e8, 2) - 2 * t_dcn) > 1e-9:
        problems.append("DCN model is not linear in wire bytes")
    if dcn_ms("v5e", "all-reduce", 1.0223e8, 1) != 0.0:
        problems.append("DCN model must price a single-slice program at "
                        "exactly zero — there is no cross-slice wire")
    # hbm_ms must be the same ruler as score()'s t_hbm_ms — the overlap
    # scorer prices interleavable compute with it, and a divergence would
    # let the two rooflines disagree about the identical byte count.
    t_hbm = hbm_ms("v5e", 1.435e11)
    if abs(t_hbm - score("v5e", flops=0.0,
                         bytes_accessed=1.435e11)["t_hbm_ms"]) > 0.05:
        problems.append(f"hbm_ms diverged from score()'s t_hbm_ms ruler: "
                        f"{t_hbm:.2f} ms on the §2 anchor bytes")
    return problems
