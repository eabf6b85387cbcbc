"""ops.ring_store: every slot's new row into its ring in one pass.

The yardstick is the write the decode step made before the kernel: rings
of ``[slots, capacity, *row]`` and ``vmap(dynamic_update_slice)`` at each
slot's own index.  Whatever runs the store — the Pallas kernel (under the
interpreter here) or the composition that stands in where a ring does not
tile — the whole ring afterwards equals that one bit for bit, the entries
the store does not own included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpuframe.ops import kernel_impl, ring_store as rs

# (id, ring shape [slots, *row, capacity], how the store is run)
LAYOUTS = [
    ("heads_tiled-interpret", (5, 4, 16, 256), "interpret"),
    ("heads_tiled-standin", (5, 4, 16, 256), "xla"),
    ("flat_129_slots-interpret", (129, 32, 128), "interpret"),
    ("capacity_48-standin", (3, 4, 16, 48), "dispatch"),
    ("row_of_4-standin", (3, 4, 256), "dispatch"),
]


def _lengths(case, slots, capacity):
    """Tokens already cached per slot, before each step of the case."""
    s = np.arange(slots)
    if case == "distinct":
        return [(s * 37 + 5) % capacity]
    if case == "first_and_last":
        return [np.where(s % 2 == 0, 0, capacity - 1)]
    if case == "wraparound":        # length >= capacity: index length % cap
        return [capacity + (s * 29) % capacity, 3 * capacity + s * 0]
    if case == "two_steps":         # consecutive steps, crossing a lane block
        first = (s * 37 + 127) % capacity
        return [first, first + 1]
    raise AssertionError(case)


def _old_write(ring_old, rows, idx):
    """The parent's store, on the parent's layout [slots, capacity, *row]."""
    return jax.vmap(lambda c, row, i: lax.dynamic_update_slice(
        c, row[None], (i,) + (0,) * row.ndim))(ring_old, rows, idx)


def _store(how, ring, rows, idx):
    if how == "interpret":
        assert rs.supported(ring, rows)
        return rs.ring_store(ring, rows, idx, interpret=True)
    if how == "xla":
        return rs._xla_store(ring, rows, idx)
    assert not rs.supported(ring, rows)
    return rs.ring_store(ring, rows, idx)


@pytest.mark.parametrize("case", ["distinct", "first_and_last", "wraparound",
                                  "two_steps"])
@pytest.mark.parametrize("name,shape,how", LAYOUTS,
                         ids=[lay[0] for lay in LAYOUTS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_store_equals_vmap_dynamic_update_slice(dtype, name, shape, how,
                                                case):
    slots, capacity = shape[0], shape[-1]
    key = jax.random.key(len(name) + capacity)
    ring = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    want = jnp.moveaxis(ring, -1, 1)   # the parent's layout
    for step, length in enumerate(_lengths(case, slots, capacity)):
        rows = jax.random.normal(jax.random.fold_in(key, step + 1),
                                 shape[:-1], jnp.float32).astype(dtype)
        idx = jnp.asarray(length % capacity, jnp.int32)
        ring = _store(how, ring, rows, idx)
        want = _old_write(want, rows, idx)
        got = np.asarray(jnp.moveaxis(ring, -1, 1).astype(jnp.float32))
        np.testing.assert_array_equal(
            got, np.asarray(want.astype(jnp.float32)))
        # the store's own entries hold the new rows
        np.testing.assert_array_equal(
            got[np.arange(slots), np.asarray(idx)],
            np.asarray(rows.astype(jnp.float32)))


def test_store_keeps_signed_zeros_and_non_finite_rows_apart():
    """A select, not arithmetic: -0.0 stays -0.0, and an inf or nan in one
    slot's row reaches no other slot."""
    ring = jnp.ones((3, 8, 128), jnp.float32)
    rows = jnp.stack([jnp.full((8,), -0.0), jnp.full((8,), jnp.inf),
                      jnp.full((8,), jnp.nan)]).astype(jnp.float32)
    idx = jnp.asarray([5, 5, 6], jnp.int32)
    got = np.array(rs.ring_store(ring, rows, idx, interpret=True))
    assert np.signbit(got[0, :, 5]).all() and (got[0, :, 5] == 0).all()
    assert np.isposinf(got[1, :, 5]).all() and np.isnan(got[2, :, 6]).all()
    got[0, :, 5] = got[1, :, 5] = got[2, :, 6] = 1.0
    np.testing.assert_array_equal(got, np.ones_like(got))


@pytest.mark.parametrize("shape,dtype,tiles", [
    ((64, 12, 64, 2048), jnp.bfloat16, True),    # the serving cell's ring
    ((4, 4, 16, 128), jnp.float32, True),        # tiny-lm at one block
    ((4, 4, 16, 128), jnp.bfloat16, True),       # 16 rows: one bf16 tile
    ((4, 4, 8, 128), jnp.bfloat16, False),       # 8 rows: half a bf16 tile
    ((4, 4, 16, 48), jnp.float32, False),        # the CPU tests' rings
    ((4, 4, 16, 192), jnp.float32, False),       # whole blocks of 16, not 128
    ((4, 64, 128), jnp.int8, False),             # no 8-bit rows
    ((2, 65536, 128), jnp.float32, False),       # a block that overflows VMEM
])
def test_supported_reads_the_shape(shape, dtype, tiles):
    ring = jax.ShapeDtypeStruct(shape, dtype)
    rows = jax.ShapeDtypeStruct(shape[:-1], dtype)
    assert rs.supported(ring, rows) is tiles
    assert not rs.supported(ring, jax.ShapeDtypeStruct(shape[:-1],
                                                       jnp.float16))


def test_dispatch_is_recorded(monkeypatch):
    """The run's ``kernel_impl`` record says which store ran.  On this
    backend (no Mosaic) the stand-in, whatever the ring; the kernel where
    the variable every kernel obeys asks for it — ``1`` the interpreter,
    ``0`` Mosaic, the compile for a described chip — and the ring tiles."""
    tiles = (jnp.zeros((2, 8, 128)), jnp.ones((2, 8)),
             jnp.zeros((2,), jnp.int32))
    monkeypatch.delenv("TPUFRAME_PALLAS_INTERPRET", raising=False)
    kernel_impl.reset()
    rs.ring_store(*tiles)
    assert kernel_impl._resolved["ring_store"] == {"xla": "backend=cpu"}
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    rs.ring_store(*tiles)
    assert "interpret" in kernel_impl._resolved["ring_store"]
    rs.ring_store(jnp.zeros((2, 8, 48)), jnp.ones((2, 8)),
                  jnp.zeros((2,), jnp.int32))
    assert "does not tile" in kernel_impl._resolved["ring_store"]["xla"]
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "0")
    jax.eval_shape(rs.ring_store, *tiles)   # traced, not lowered for a CPU
    assert "mosaic" in kernel_impl._resolved["ring_store"]


def test_decode_program_stores_through_the_kernel(monkeypatch):
    """A decode step on a ring that tiles holds the kernel, lowered once a
    program and not once a layer; tokens and rings are the stand-in's."""
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.serve import engine as engine_lib, kv_cache as kv

    cfg = LMConfig.tiny(vocab_size=64)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    spec = kv.spec_for_model(cfg, slots=3, capacity=128)
    layers, _ = kv.init_cache(spec)
    layers = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape), layers)
    args = (params, jnp.asarray([[1], [2], [3]], jnp.int32),
            jnp.asarray([3, 127, 64 + 128], jnp.int32), layers)

    def run(fn_name):
        decode = jax.jit(engine_lib.make_decode_fn(model))
        text = decode.lower(*args).as_text()
        assert text.count(f"func.func private @{fn_name}(") == 1
        assert text.count(f"call @{fn_name}(") == 2 * cfg.num_layers
        return decode(*args)

    want = run("_xla_store")     # this backend's choice
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    got = run("_kernel_store")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
