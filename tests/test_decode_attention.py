"""ops.decode_attention: one query per slot against the blocks its ring holds.

The yardstick is the einsum composition the decode step ran before the
kernel (``attention._xla_decode_attention``), which reads every column of
every ring and masks by ``arange < lengths``.  The Pallas kernel (under
the interpreter here) walks only the lane blocks at or below each slot's
length; whatever the slots hold, its answer is the composition's to the
rounding of a softmax summed in another order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.ops import attention, decode_attention as da, kernel_impl

# (id, rings [slots, heads, head_dim, capacity])
RINGS = [
    ("cell_sibling", (6, 12, 64, 384)),    # [64, 12, 64, 2048]'s, 3 blocks
    ("one_block", (4, 2, 64, 128)),
    ("head_128", (3, 2, 128, 256)),
    ("head_32", (5, 4, 32, 256)),          # four heads fold into one vreg
]


def _lengths(case, slots, capacity):
    s = np.arange(slots)
    if case == "all_one":               # every slot idle
        return np.ones(slots, np.int32)
    if case == "all_full":              # every slot wrapped
        return np.full(slots, capacity, np.int32)
    if case == "block_edges":
        return np.minimum(np.asarray([127, 128, 129, 255, 256, 257])[s % 6],
                          capacity).astype(np.int32)
    if case == "idle_and_full":
        return np.where(s % 2 == 0, 1, capacity).astype(np.int32)
    raise AssertionError(case)


def _operands(shape, dtype, seed=0):
    b, n, d, _ = shape
    key = jax.random.key(seed)
    q = jax.random.normal(key, (b, 1, n, d), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, r), shape, jnp.float32)
            for r in (1, 2))
    return tuple(a.astype(dtype) for a in (q, k, v))


@pytest.mark.parametrize("case", ["all_one", "all_full", "block_edges",
                                  "idle_and_full"])
@pytest.mark.parametrize("name,shape", RINGS, ids=[r[0] for r in RINGS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_equals_the_einsums(dtype, name, shape, case):
    q, k, v = _operands(shape, dtype, seed=len(name))
    lengths = jnp.asarray(_lengths(case, shape[0], shape[-1]))
    assert da.supported(q, k)
    got = da.decode_attention(q, k, v, lengths, interpret=True)
    want = attention._xla_decode_attention(q, k, v, lengths)
    assert got.shape == want.shape == q.shape and got.dtype == want.dtype
    # float32: the sums' order; bfloat16: one rounding of the probabilities
    # (unnormalized here, normalized there) and one of the result
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_columns_past_a_slots_length_are_never_read_into_the_answer():
    """What lies at and beyond ``lengths`` — an earlier request's rows, of
    any finite size — moves nothing, in the slot's last block or past it."""
    shape = (3, 2, 64, 256)
    q, k, v = _operands(shape, jnp.float32)
    lengths = np.asarray([1, 100, 129], np.int32)
    beyond = np.arange(shape[-1])[None, :] >= lengths[:, None]
    wild = jnp.where(beyond[:, None, None, :], 1e30, 0.0)
    got = da.decode_attention(q, k, v, jnp.asarray(lengths), interpret=True)
    got_wild = da.decode_attention(q, k + wild, v - wild,
                                   jnp.asarray(lengths), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got_wild))
    # one valid column: the answer is that column of V
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(v[0, :, :, 0]), rtol=1e-6)


@pytest.mark.parametrize("shape,dtype,tiles", [
    ((64, 12, 64, 2048), jnp.bfloat16, True),    # the serving cell's rings
    ((128, 12, 64, 2048), jnp.float32, True),    # 128 slots of float32
    ((4, 2, 64, 128), jnp.float32, True),        # one block a slot
    ((4, 2, 128, 256), jnp.bfloat16, True),      # a head of whole lanes
    ((4, 4, 16, 128), jnp.float32, False),       # tiny-lm: 64 lanes of heads
    ((4, 4, 8, 128), jnp.bfloat16, False),       # a head of half a bf16 tile
    ((4, 2, 64, 48), jnp.float32, False),        # the CPU tests' capacities
    ((4, 2, 64, 192), jnp.float32, False),       # whole blocks of 64, not 128
    ((4, 2, 64, 128), jnp.int8, False),          # no 8-bit rings
    ((64, 32, 128, 2048), jnp.bfloat16, False),  # queries beyond VMEM
])
def test_supported_reads_the_shape(shape, dtype, tiles):
    b, n, d, _ = shape
    q = jax.ShapeDtypeStruct((b, 1, n, d), dtype)
    ring = jax.ShapeDtypeStruct(shape, dtype)
    assert da.supported(q, ring) is tiles
    assert not da.supported(jax.ShapeDtypeStruct((b, 1, n, d), jnp.float16),
                            ring)


def test_dispatch_is_recorded(monkeypatch):
    """The run's ``kernel_impl`` record says which attention ran.  On this
    backend (no Mosaic) the einsums, whatever the rings; the kernel where
    the variable every kernel obeys asks for it — ``1`` the interpreter,
    ``0`` Mosaic, the compile for a described chip — and the rings tile."""
    q, k, v = _operands((2, 2, 64, 128), jnp.float32)
    lengths = jnp.asarray([1, 128], jnp.int32)
    monkeypatch.delenv("TPUFRAME_PALLAS_INTERPRET", raising=False)
    kernel_impl.reset()
    want = attention.decode_attention(q, k, v, lengths=lengths)
    assert kernel_impl._resolved["decode_attention"] == {
        "xla": "backend=cpu"}
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    got = attention.decode_attention(q, k, v, lengths=lengths)
    assert "blocks [2, 64, 128]" in \
        kernel_impl._resolved["decode_attention"]["interpret"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    attention.decode_attention(q[..., :48], k[:, :, :48], v[:, :, :48],
                               lengths=lengths)
    assert "do not tile" in kernel_impl._resolved["decode_attention"]["xla"]
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "0")
    jax.eval_shape(lambda *a: attention.decode_attention(
        *a, lengths=lengths), q, k, v)   # traced, not lowered for a CPU
    assert "mosaic" in kernel_impl._resolved["decode_attention"]


def test_entry_refuses_what_is_not_a_decode_query():
    q, k, v = _operands((2, 2, 64, 128), jnp.float32)
    lengths = jnp.asarray([1, 2], jnp.int32)
    with pytest.raises(ValueError, match=r"q \[B, 1, N, D\]"):
        attention.decode_attention(jnp.concatenate([q, q], 1), k, v,
                                   lengths=lengths)
    with pytest.raises(ValueError, match="rings"):
        attention.decode_attention(q, k, v[:, :1], lengths=lengths)


def test_decode_program_attends_through_the_kernel(monkeypatch):
    """A decode step on rings that tile holds the kernel, lowered once a
    program and not once a layer; its tokens are the stand-in's and its
    logits the stand-in's to float32 rounding, idle slots beside live."""
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.serve import engine as engine_lib, kv_cache as kv

    cfg = LMConfig(vocab_size=64, hidden_size=128, num_layers=2,
                   num_heads=4, intermediate_size=128, max_seq=256)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    spec = kv.spec_for_model(cfg, slots=4, capacity=256)
    layers, _ = kv.init_cache(spec)
    layers = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape), layers)
    args = (params, jnp.asarray([[1], [2], [3], [4]], jnp.int32),
            jnp.asarray([0, 127, 128, 256 + 5], jnp.int32), layers)

    def run(fn_name):
        decode = jax.jit(engine_lib.make_decode_fn(model))
        text = decode.lower(*args).as_text()
        assert text.count(f"func.func private @{fn_name}(") == 1
        assert text.count(f"call @{fn_name}(") == cfg.num_layers
        return decode(*args)

    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    got_toks, got_lengths, got_layers = run("_launch")
    monkeypatch.delenv("TPUFRAME_PALLAS_INTERPRET")
    want_toks, want_lengths, want_layers = jax.jit(
        engine_lib.make_decode_fn(model))(*args)
    np.testing.assert_array_equal(np.asarray(got_toks),
                                  np.asarray(want_toks))
    # the idle slot stays at 0; the others move on, the wrapped one too
    np.testing.assert_array_equal(np.asarray(got_lengths),
                                  [0, 128, 129, 256 + 6])
    np.testing.assert_array_equal(np.asarray(want_lengths),
                                  np.asarray(got_lengths))
    for a, b in zip(jax.tree.leaves(got_layers),
                    jax.tree.leaves(want_layers)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
