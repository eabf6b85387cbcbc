"""Paged/ring KV-cache for the decode path (tpuframe.serve).

The cache is the serving counterpart of a training batch: per layer one
``(k, v)`` pair of ``[slots, num_heads, head_dim, capacity]`` arrays
plus a ``lengths [slots]`` vector counting tokens already cached per
slot.  The capacity axis is minor — one token is a column of
``num_heads x head_dim`` numbers — which is the layout decode attention
reads at the chip's bandwidth (its reductions run over whole lanes of
cached tokens) and the one whose row-major form a Mosaic kernel can take
as it is (a ring of ``[.., capacity, 12, 64]`` would reach it padded to
16 x 128, 2.7 times the memory).
It is deliberately a *plain pytree of arrays*, not an object the model
mutates: the engine threads it functionally through the AOT-compiled
prefill/decode executables (arrays in, updated arrays out), which is
what makes buffer donation legal.  With the buffers donated, insert's
slot copy and the decode step's store (``ops.ring_store``: every slot's
new column in one pass over the slots, one lane block in and out per
slot) update HBM in place; nothing ring-sized is copied.

Ring semantics: the model writes token ``t`` at index ``t % capacity``
and masks attention to ``min(t + 1, capacity)`` valid entries, so a
sequence that outlives its bucket degrades to sliding-window attention
over the last ``capacity`` tokens instead of faulting.  Keys are stored
post-RoPE, so a wrapped slot keeps the absolute position it was written
with (see ``models/transformer_lm.py:CausalSelfAttention``).

Shape bucketing lives here too: every compiled shape (prompt buckets,
KV capacity) is a multiple of the decode block, so the engine's AOT
table is a small closed set and the persistent compile cache (PR 3) can
amortize warmup across restarts.  Bucket sets resolve env > tune-DB >
default (``TPUFRAME_SERVE_BUCKETS`` / ``TPUFRAME_DECODE_BLOCK``, the
PR 3/5 precedence idiom via ``tune.db``).
"""

from __future__ import annotations

from dataclasses import dataclass

# Hard defaults — what a plain CPU run (no env, no tune DB) sees.  128
# matches the flash-attention default block edge and the (8, 128) TPU
# tile; prompt buckets are powers of two over it so padding waste is
# bounded at 2x worst-case.
DEFAULT_DECODE_BLOCK = 128
DEFAULT_PROMPT_BUCKETS = (128, 256, 512)
# Columns of one lane block of a ring: the unit decode attention reads a
# slot's ring in (ops/decode_attention.py), and the scheduler counts in.
KV_BLOCK = 128


@dataclass(frozen=True)
class CacheSpec:
    """Static shape contract of one engine's cache — everything the AOT
    table is keyed on."""

    slots: int           # decode batch size (concurrent sequences)
    capacity: int        # KV entries per slot (ring length)
    num_layers: int
    num_heads: int
    head_dim: int
    dtype: str = "float32"

    def __post_init__(self):
        if self.capacity % 8:
            raise ValueError(f"capacity {self.capacity} not a multiple of "
                             f"8 (TPU sublane alignment)")
        if self.slots < 1:
            raise ValueError(f"need at least one slot, got {self.slots}")

    def layer_shape(self) -> tuple:
        """One layer's K (or V) ring: a token is a column."""
        return (self.slots, self.num_heads, self.head_dim, self.capacity)

    def bytes_per_token(self) -> int:
        """HBM bytes one cached token costs across all layers (K + V) —
        the ``kv_bytes_per_token`` input of the decode roofline
        (tune/roofline.decode_score)."""
        import numpy as np

        itemsize = np.dtype(self.dtype).itemsize
        return 2 * self.num_layers * self.num_heads * self.head_dim \
            * itemsize

    def total_bytes(self) -> int:
        return self.slots * self.capacity * self.bytes_per_token()


def init_cache(spec: CacheSpec):
    """Zeroed per-layer ``(k, v)`` pairs + zero lengths — the engine's
    reset state.  Returns ``(layers, lengths)``."""
    import jax.numpy as jnp

    shape = spec.layer_shape()
    dtype = jnp.dtype(spec.dtype)
    layers = tuple((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                   for _ in range(spec.num_layers))
    lengths = jnp.zeros((spec.slots,), jnp.int32)
    return layers, lengths


def spec_for_model(cfg, *, slots: int, capacity: int) -> CacheSpec:
    """CacheSpec derived from an ``LMConfig`` (single source for the
    layer geometry — the spec can never disagree with the model)."""
    return CacheSpec(slots=slots, capacity=capacity,
                     num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                     head_dim=cfg.head_dim, dtype=cfg.dtype)


# ---------------------------------------------------------------------------
# Shape buckets — the closed set of compiled shapes.
# ---------------------------------------------------------------------------

def parse_buckets(text: str) -> tuple:
    """``"64,128,256"`` -> ``(64, 128, 256)`` (sorted, deduplicated).
    The TPUFRAME_SERVE_BUCKETS wire format."""
    vals = sorted({int(v) for v in text.replace(";", ",").split(",")
                   if v.strip()})
    if not vals:
        raise ValueError(f"no buckets in {text!r}")
    if any(v < 8 or v % 8 for v in vals):
        raise ValueError(f"buckets must be multiples of 8, got {vals}")
    return tuple(vals)


def resolve_buckets(default=DEFAULT_PROMPT_BUCKETS) -> tuple:
    """Prompt-length buckets: env > tune-DB > default (tune.db owns the
    precedence chain so serving and training resolve identically)."""
    from tpuframe.tune import db as tune_db

    return tune_db.resolve_serve_buckets(tuple(default))


def resolve_decode_block(default: int = DEFAULT_DECODE_BLOCK) -> int:
    """KV-capacity granularity: env > tune-DB > default."""
    from tpuframe.tune import db as tune_db

    return tune_db.resolve_decode_block(default)


def bucket_for(length: int, buckets) -> int:
    """Smallest bucket that fits ``length``.  Raises when the request
    exceeds every bucket — admission control's job is to reject it
    BEFORE any compile-shape decision, never to pick a silent new
    shape (that is exactly the recompile-per-request failure mode the
    TF109 lint guards)."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(f"prompt length {length} exceeds largest bucket "
                     f"{max(buckets)} — reject at admission")


def capacity_for(max_context: int, decode_block: int) -> int:
    """KV capacity for a target context: round up to the decode block so
    every compiled capacity is block-quantized."""
    if max_context < 1:
        raise ValueError(f"max_context must be positive, got {max_context}")
    blocks = (max_context + decode_block - 1) // decode_block
    return blocks * decode_block


def check_buckets(buckets, capacity: int) -> list:
    """Invariants the analysis-gate self-check enforces.  Returns
    problem strings; [] means healthy."""
    problems = []
    bl = tuple(buckets)
    if bl != tuple(sorted(set(bl))):
        problems.append(f"buckets not sorted/unique: {bl}")
    if any(b < 8 or b % 8 for b in bl):
        problems.append(f"buckets not multiples of 8: {bl}")
    if bl and max(bl) > capacity:
        problems.append(f"largest bucket {max(bl)} exceeds KV capacity "
                        f"{capacity} — prefill would overrun the ring")
    if capacity % 8:
        problems.append(f"capacity {capacity} not a multiple of 8")
    return problems
