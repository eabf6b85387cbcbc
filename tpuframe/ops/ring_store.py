"""One row per slot into a ring, at each slot's own index, in one pass.

The decode step's KV write (tpuframe.serve): every slot of the decode
batch appends one new row to its ring at ``length % capacity``.  A ring is
``[slots, *row, capacity]`` — the capacity axis minor, so one token is a
*column* — and the write is in place on the donated buffer.  The op knows
nothing of heads: a row is whatever lies between the slot and the
capacity axis (``[heads, head_dim]`` of K or V, a latent, a state).

Written as ``vmap(dynamic_update_slice)`` this is a scatter, and the TPU
compiler expands a scatter into a ``while`` loop of one iteration per
slot, nine small ops each: 6.6 us an iteration, 10 ms of a 19.5 ms decode
step at 64 slots x 24 tensors (PERF.md §6, PR 30).  The Mosaic kernel
here has a grid over slots with the write indices scalar-prefetched: a
grid step reads the one ``[*row, 128]`` lane block that holds the slot's
index, replaces one lane column by a select and writes the block back,
the ring aliased from input to output.  Its cost follows what it writes:
one block in and out per slot.

Where the ring does not tile (a capacity that is no multiple of 128, a
row whose last dimension is no multiple of the sublane tile), and on a
backend that has no Mosaic, the ``vmap`` composition stands in.  The
choice is made from the shapes and the backend and is recorded
(``ops.kernel_impl``); there is no knob of its own
(``TPUFRAME_PALLAS_INTERPRET``, as for every kernel, says to lower Mosaic
for a described chip from a CPU host, or to run the interpreter).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Six blocks live at once (rows, ring in, ring out, each double-buffered);
# kept well inside the 16 MiB of VMEM a kernel gets by default.
_VMEM_BUDGET = 12 << 20


def supported(ring: jax.Array, rows: jax.Array) -> bool:
    """True when ``ring [slots, *row, capacity]`` tiles for the kernel:
    whole lane blocks along the capacity, whole sublane tiles along the
    row's last dimension (8 sublanes of 32 bits: 8 float32, 16 bfloat16),
    and a block that fits VMEM."""
    if ring.ndim < 3 or rows.shape != ring.shape[:-1] \
            or rows.dtype != ring.dtype:
        return False
    itemsize = ring.dtype.itemsize
    if itemsize not in (2, 4):
        return False
    capacity, width = ring.shape[-1], math.prod(ring.shape[1:-1])
    return (capacity % _LANES == 0
            and ring.shape[-2] % (32 // itemsize) == 0
            and 6 * width * _LANES * itemsize <= _VMEM_BUDGET)


def ring_store(ring: jax.Array, rows: jax.Array, idx: jax.Array, *,
               interpret: bool | None = None) -> jax.Array:
    """``ring[s, ..., idx[s]] = rows[s]`` for every slot ``s``; every
    other entry unchanged.  ``ring [slots, *row, capacity]``, ``rows
    [slots, *row]`` of the ring's dtype, ``idx [slots]`` int32 in ``[0,
    capacity)``."""
    from tpuframe.ops import kernel_impl

    if not supported(ring, rows):
        why = (f"ring {ring.shape} {ring.dtype} rows {rows.shape} "
               f"{rows.dtype} does not tile")
    else:
        why = kernel_impl.no_mosaic() if interpret is None else None
    if why is not None:
        kernel_impl.record("ring_store", "xla", why)
        return _xla_store(ring, rows, idx)
    interpret = kernel_impl.resolve_interpret(
        "ring_store", interpret,
        f"grid ({ring.shape[0]},) block {ring.shape[1:-1] + (_LANES,)}")
    return _kernel_store(ring, rows, idx, interpret=interpret)


@jax.jit
def _xla_store(ring, rows, idx):
    """The composition the kernel replaces: a scatter, which the TPU
    compiler runs as a loop over slots."""
    return jax.vmap(lambda r, row, i: lax.dynamic_update_slice(
        r, row[..., None], (0,) * row.ndim + (i,)))(ring, rows, idx)


def _lane_columns(rows):
    """``rows [slots, *row]`` as lane columns ``[*row, slots]``, the slots
    padded to whole lane blocks: a tiny transpose XLA folds into whatever
    made the rows."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, -rows.shape[0] % _LANES)]
    return jnp.pad(jnp.moveaxis(rows, 0, -1), pad)


def _with_column(block, cols, lane, src):
    """``block [*row, 128]`` with its lane column ``lane`` replaced by lane
    column ``src`` of ``cols [*row, 128]``: the column rotated under the
    lane it goes to, then a select.  The rotate wants two dimensions of 32
    bits: the row's dimensions fold into the sublanes, and narrower
    numbers ride through it packed in pairs."""
    shift = lax.rem(lane - src + _LANES, _LANES)
    cols = cols.reshape(-1, _LANES)
    if cols.dtype.itemsize < 4:
        cols = pltpu.bitcast(
            pltpu.roll(pltpu.bitcast(cols, jnp.uint32), shift, 1),
            cols.dtype)
    else:
        cols = pltpu.roll(cols, shift, 1)
    lanes = lax.broadcasted_iota(jnp.int32, block.shape, block.ndim - 1)
    return jnp.where(lanes == lane, cols.reshape(block.shape), block)


def _store_kernel(idx_ref, cols_ref, ring_ref, out_ref):
    """One slot: ``cols_ref [*row, 128]`` holds the rows of 128 slots as
    lane columns, this slot's at lane ``s % 128``; ``ring_ref``/``out_ref
    [1, *row, 128]`` are the lane block of this slot's ring that holds
    its write index."""
    s = pl.program_id(0)
    out_ref[0] = _with_column(ring_ref[0], cols_ref[...],
                              lax.rem(idx_ref[s], _LANES),
                              lax.rem(s, _LANES))


# A jit of its own, all but the arrays static: a program that stores in
# every layer traces and lowers the kernel once, not once a layer.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_store(ring, rows, idx, *, interpret: bool):
    slots, row = ring.shape[0], ring.shape[1:-1]
    zeros = (0,) * len(row)
    ring_spec = pl.BlockSpec((1,) + row + (_LANES,),
                             lambda s, idx: (s,) + zeros + (idx[s] // _LANES,))
    return pl.pallas_call(
        _store_kernel,
        name="ring_store",   # the op's name in a profiler trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots,),
            in_specs=[
                pl.BlockSpec(row + (_LANES,),
                             lambda s, idx: zeros + (s // _LANES,)),
                ring_spec,
            ],
            out_specs=ring_spec),
        out_shape=jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        input_output_aliases={2: 0},   # the ring, past the prefetched idx
        interpret=interpret,
    )(idx, _lane_columns(rows), ring)
