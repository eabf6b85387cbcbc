"""One query token per slot against the KV rings, reading what the slots hold.

The decode step's attention (tpuframe.serve): every slot of the decode
batch has one new query and a ring of cached keys and values, ``[slots,
heads, head_dim, capacity]`` with the capacity axis minor — a token is a
column (serve/kv_cache.py) — of which the first ``lengths[s]`` columns
are valid.  Written as two einsums over the ring this reads all of every
ring every step, whatever the slots hold: at 64 slots of 2048 columns,
4.8 GB a step for well under 1 GB of live keys and values (PERF.md §6,
PR 32).

The Mosaic kernel here is ONE invocation that walks, slot by slot, only
the ``ceil(lengths[s] / block)`` lane blocks that hold valid columns:
the rings stay in HBM, ``lengths`` is scalar-prefetched, and a ring of
VMEM buffers is kept full by DMAs that the kernel starts itself, several
blocks ahead and across the slots' edges, so a slot that holds one block
(an idle one) costs one block and not a DMA's latency.  There is no grid
over blocks: a grid step costs about as much as a block's worth of work
(PR 26).  Per block it is the flash forward's recurrence at query length
1 — scores, a running maximum and sum in float32, the products' operands
in the ring's dtype — with the tail of a slot's last block masked by
``column < lengths``.

All heads go through the MXU at once.  The block ``[heads, head_dim,
block]`` is, untouched, the matrix ``[heads * head_dim, block]``; the
query enters block-diagonal, ``[rows, heads * head_dim]`` with head
``n``'s query in row ``n`` at columns ``n * head_dim ..``, so one product
gives every head's scores ``[rows, block]`` with the heads on sublanes,
and ``probs [rows, block]`` against the value block contracts the lanes
of both into ``[rows, heads * head_dim]``, whose diagonal blocks are the
heads' outputs.  The MXU's time goes by the weights it loads (the block),
not by the rows pushed through them, so the off-diagonal work is free and
the softmax's statistics are one tile for all heads.

The loop body is straight-line so that the compiler overlaps the scores
of block ``t + 1`` with the softmax and the value product of block ``t``
(the scores ride in the loop's carry): state is reset by a select at a
slot's first block and the slot's output row is rewritten after every
block, the last write standing.

Where the rings do not tile (a capacity that is no multiple of 128, a
head size that is no whole sublane tile or does not fold into lanes, a
working set beyond VMEM), and on a backend that has no Mosaic, the
einsum composition stands in.  The choice is made from the shapes and the
backend and is recorded (``ops.kernel_impl``); there is no knob of its
own (``TPUFRAME_PALLAS_INTERPRET``, as for every kernel, says to lower
Mosaic for a described chip from a CPU host, or to run the interpreter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Lane blocks a DMA brings (columns of one slot's K and of its V), and the
# VMEM buffers they land in: one being read for its values, one for its
# keys, the others in flight.
_BLOCK = 128
_BUFFERS = 4
_VMEM_BUDGET = 12 << 20
_MASKED = -1e30


def _rows(heads: int, itemsize: int) -> int:
    """Heads rounded up to whole sublane tiles of the ring's dtype."""
    tile = 32 // itemsize
    return -(-heads // tile) * tile


def supported(q: jax.Array, k_cache: jax.Array) -> bool:
    """True when ``q [B, 1, N, D]`` and the rings ``[B, N, D, S]`` tile for
    the kernel: 16- or 32-bit, whole 128-lane blocks along the capacity, a
    head size of whole sublane tiles (8 of 32 bits: 8 float32, 16
    bfloat16) that folds into whole lanes, and the query, the output and
    the block buffers inside VMEM."""
    if k_cache.ndim != 4 or q.dtype != k_cache.dtype:
        return False
    b, n, d, s = k_cache.shape
    itemsize = k_cache.dtype.itemsize
    if itemsize not in (2, 4) or s % _LANES or d % (32 // itemsize):
        return False
    if d % _LANES and (_LANES % d or (n * d) % _LANES):
        return False
    rows = _rows(n, itemsize)
    working = (b * rows * n * d * itemsize              # the queries
               + b * rows * max(d, _LANES) * 4          # the outputs
               + 2 * _BUFFERS * n * d * _BLOCK * itemsize
               + 4 * rows * n * d * 4)                  # the loop's carry
    return working <= _VMEM_BUDGET


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, interpret: bool) -> jax.Array:
    """``softmax(q . K[:, :lengths] / sqrt(D)) . V[:, :lengths]`` per slot
    and head.  ``q [B, 1, N, D]``, rings ``[B, N, D, S]`` of q's dtype,
    ``lengths [B]`` int32 in ``[1, S]``; returns ``[B, 1, N, D]``."""
    return _launch(q, k_cache, v_cache, lengths.astype(jnp.int32),
                   block=_BLOCK, buffers=_BUFFERS, interpret=interpret)


def describe(k_cache: jax.Array) -> str:
    """What the kernel chose for these rings, for the run's record."""
    b, n, d, s = k_cache.shape
    return (f"slots {b} blocks [{n}, {d}, {_BLOCK}] of {s // _BLOCK} a "
            f"slot, {_BUFFERS} buffers")


def _heads_diagonal(acc, head_dim: int):
    """``acc [rows, heads * head_dim]`` -> ``[rows, head_dim]``: row ``n``'s
    own block ``n``.  The other blocks are zeroed and the blocks summed —
    whole vregs first, then the lanes folded onto the first ``head_dim``."""
    rows, width = acc.shape
    row = lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    col = lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    own = (col >= row * head_dim) & (col < (row + 1) * head_dim)
    acc = jnp.where(own, acc, 0.0)
    chunk = max(head_dim, _LANES)
    out = acc[:, :chunk]
    for at in range(chunk, width, chunk):
        out = out + acc[:, at:at + chunk]
    fold = chunk
    while fold > head_dim:
        fold //= 2
        out = out + pltpu.roll(out, chunk - fold, 1)
    return out[:, :head_dim]


def _attend_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, *,
                   block: int, buffers: int):
    """``len_ref [B]`` in SMEM; ``q_ref [B, rows, N * D]`` block-diagonal
    queries and ``o_ref [B, rows, D]`` in VMEM; the rings in HBM; ``kbuf``,
    ``vbuf [buffers, N, D, block]``; ``sems [2, buffers]``."""
    slots, heads, head_dim, capacity = k_hbm.shape
    width = heads * head_dim
    ahead = buffers - 1

    def blocks_of(s):   # never past the ring, whatever the length says
        return jnp.clip(lax.div(len_ref[s] + block - 1, block), 1,
                        capacity // block)

    def after(s, i):
        """The walk's next (slot, block); it stays on the last one."""
        last = i + 1 >= blocks_of(s)
        at_end = last & (s + 1 >= slots)
        return (jnp.where(last & ~at_end, s + 1, s),
                jnp.where(at_end, i, jnp.where(last, 0, i + 1)))

    def copies(s, i, buf):
        at = pl.multiple_of(i * block, block)
        return [pltpu.make_async_copy(
            ring.at[s, :, :, pl.ds(at, block)], dst.at[buf], sems.at[j, buf])
            for j, (ring, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def wait(buf):
        for copy in copies(0, 0, buf):
            copy.wait()

    def scores(s, i, buf):
        k = kbuf[buf].reshape(width, block)
        sc = jnp.dot(q_ref[s], k, preferred_element_type=jnp.float32)
        col = i * block + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        return jnp.where(col < len_ref[s], sc, _MASKED)

    total = lax.fori_loop(0, slots, lambda s, n: n + blocks_of(s),
                          jnp.int32(0))
    # Steps 0 .. ahead - 1 set off; the walk past the last block repeats
    # it, so every start has its wait and nothing hangs on a short walk.
    ps, pi = jnp.int32(0), jnp.int32(0)
    for step in range(ahead):
        for copy in copies(ps, pi, step):
            copy.start()
        ps, pi = after(ps, pi)
    wait(0)
    rows = q_ref.shape[1]
    init = (jnp.int32(0), jnp.int32(0), ps, pi,
            scores(jnp.int32(0), jnp.int32(0), 0),
            jnp.full((rows, 1), _MASKED, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, width), jnp.float32))

    def body(t, carry):
        s, i, ps, pi, sc, m, l, acc = carry
        for copy in copies(ps, pi, lax.rem(t + ahead, buffers)):
            copy.start()
        nxt = lax.rem(t + 1, buffers)
        wait(nxt)
        s1, i1 = after(s, i)
        sc1 = scores(s1, i1, nxt)          # block t + 1, under block t's:
        first = i == 0
        m = jnp.where(first, _MASKED, m)
        l = jnp.where(first, 0.0, l)
        acc = jnp.where(first, 0.0, acc)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        v = vbuf[lax.rem(t, buffers)].reshape(width, block)
        acc = alpha * acc + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[s] = _heads_diagonal(acc, head_dim) / l
        ps, pi = after(ps, pi)
        return s1, i1, ps, pi, sc1, m_new, l, acc

    lax.fori_loop(0, total, body, init)
    # Waited so far: steps 0 .. total; started: 0 .. total + ahead - 1.

    def drain(step, carry):
        wait(lax.rem(step, buffers))
        return carry

    lax.fori_loop(total + 1, total + ahead, drain, 0)


# A jit of its own, all but the arrays static: a program that attends in
# every layer traces and lowers the kernel once, not once a layer.
@functools.partial(jax.jit,
                   static_argnames=("block", "buffers", "interpret"))
def _launch(q, k_cache, v_cache, lengths, *, block: int, buffers: int,
            interpret: bool):
    b, n, d, _ = k_cache.shape
    dtype = k_cache.dtype
    rows = _rows(n, dtype.itemsize)
    scale = 1.0 / jnp.sqrt(d).astype(q.dtype)
    # Block-diagonal queries [B, rows, N * D]: a few KB a slot, and XLA
    # folds it into whatever made q.
    own = jnp.eye(rows, n, dtype=bool)[None, :, :, None]
    q_diag = jnp.where(own, (q * scale)[:, 0][:, None], 0).reshape(
        b, rows, n * d)
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, lengths: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_attend_kernel, block=block, buffers=buffers),
        name="decode_attention",   # the op's name in a profiler trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[whole(b, rows, n * d), hbm, hbm],
            out_specs=whole(b, rows, d),
            scratch_shapes=[
                pltpu.VMEM((buffers, n, d, block), dtype),
                pltpu.VMEM((buffers, n, d, block), dtype),
                pltpu.SemaphoreType.DMA((2, buffers))]),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), jnp.float32),
        interpret=interpret,
    )(lengths, q_diag, k_cache, v_cache)
    return out[:, None, :n, :].astype(dtype)
