"""Compile-only guards for the chip — no chip needed.

The TPU's compiler is installed beside jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  These cases run
the whole compiler — Mosaic kernel codegen included — on the kernels of the
main path at the widths ``chip_smoke.py`` runs them, about two seconds
each, in this process.  What the compiler refuses here it refuses on the
chip, and costs no chip time.  Nothing executes: results and times still
need ``chip_smoke.py`` on a chip.

The slower whole-program compiles (ResNet-50 dp4 step, the sweeps' CLI)
stay in ``tests/test_aot_tpu_compile.py`` behind ``slow``.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e(v5e_topology):
    """One described v5e device (of a 2x2), as a sharding for shapes."""
    return SingleDeviceSharding(v5e_topology.devices[0])


@pytest.fixture(autouse=True)
def chip_target(monkeypatch, no_persistent_compile_cache):
    """Compile as the chip would: its generation named (the process sees a
    CPU) and the tuning DB left out."""
    monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
    monkeypatch.setenv("TPUFRAME_TUNE_DB", "off")


def _compile_grad(loss, *args):
    c = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
        *args).compile()
    assert "tpu_custom_call" in c.as_text(), "no Mosaic kernel in the program"
    return c


@pytest.mark.parametrize("shape", [
    (8, 2048, 12, 64),   # the 124M LM's step at b8 x 2048 (chip_smoke)
    (1, 8192, 12, 64),   # the 8k cell XLA attention cannot compile
])
@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_mha", "flash_mha_lse"])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, shape, with_lse):
    from tpuframe.ops.flash_attention import flash_mha, flash_mha_lse

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)

    def loss(q, k, v):
        if with_lse:  # the ring-stage variant: lse out, its cotangent in
            out, lse = flash_mha_lse(q, k, v, causal=True, interpret=False)
            return out.astype(jnp.float32).sum() + (lse * 0.5).sum()
        return flash_mha(q, k, v, causal=True,
                         interpret=False).astype(jnp.float32).sum()

    c = _compile_grad(loss, q, q, q)
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1 << 30  # no S x S scores in HBM


@pytest.mark.parametrize("shape,s_kv,dtype,causal,masked", [
    ((4, 2048, 6, 128), 2048, jnp.bfloat16, True, False),   # head size 128
    ((1, 8192, 2, 256), 8192, jnp.bfloat16, True, False),   # the widest head
    ((1, 32768, 2, 64), 32768, jnp.bfloat16, True, False),  # K/V in blocks
    ((1, 32768, 1, 128), 32768, jnp.float32, True, True),   # f32, key mask
    ((16, 512, 12, 64), 512, jnp.bfloat16, False, True),    # an encoder
    ((2, 640, 4, 64), 1152, jnp.bfloat16, True, True),      # 128-only sizes
])
def test_flash_rule_choice_compiles_for_v5e(v5e, shape, s_kv, dtype, causal,
                                            masked):
    """The tiling ``choose_tiles`` picks — blocks up to 1024 rows, the walked
    operand whole or in blocks of up to 16k, ``vmem_limit_bytes`` raised
    where its arithmetic passes 16 MiB — is one Mosaic takes, forward and
    both backward kernels, across the shapes the rule has to serve."""
    from tpuframe.ops import flash_attention as fa

    b, _, n, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    k = jax.ShapeDtypeStruct((b, s_kv, n, d), dtype, sharding=v5e)
    mask = jax.ShapeDtypeStruct((b, s_kv), jnp.int32, sharding=v5e)
    assert fa.supported(q, k)

    def loss(q, k, v, mask):
        return fa.flash_mha(q, k, v, mask=mask if masked else None,
                            causal=causal,
                            interpret=False).astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k, mask).compile()
    assert c.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_window_grouped_heads_compile_for_v5e(v5e, window):
    """The afmoe block's attention at one chip's share: 8192 tokens, 32
    query heads of 128 over 4 K/V heads read where they lie, with and
    without the 2048-token window; dK and dV come out per K/V head."""
    from tpuframe.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=v5e)
    k = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=v5e)
    assert fa.supported(q, k)

    def loss(q, k, v):
        return fa.flash_mha(q, k, v, causal=True, window=window,
                            interpret=False).astype(jnp.float32).sum()

    c = _compile_grad(loss, q, k, k)
    assert c.as_text().count("tpu_custom_call") >= 3
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_flash_latent_attention_compiles_for_v5e(v5e):
    """The deepseek_v3 block's attention at one chip's share: 8192 tokens,
    32 heads whose scores are a 128-deep and a 64-deep product (the rotary
    key held once a position, ``[1, 8192, 1, 64]``) and whose values are
    128 wide: forward and both backward kernels, dk_rope out per position."""
    from tpuframe.ops import flash_attention as fa

    def sds(n, d):
        return jax.ShapeDtypeStruct((1, 8192, n, d), jnp.bfloat16,
                                    sharding=v5e)

    args = (sds(32, 128), sds(32, 64), sds(32, 128), sds(1, 64),
            sds(32, 128))
    assert fa.mla_supported(*args)

    def loss(*a):
        return fa.flash_mla(*a, interpret=False).astype(jnp.float32).sum()

    c = _compile_grad(loss, *args)
    text = c.as_text()
    for name in ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv"):
        assert len(_kernel_calls(text, name)) == 1, name
    # the rotary key goes in, and its gradient comes out, one row a position
    assert "bf16[1,8192,64]" in _kernel_calls(text, "flash_mla_bwd_dkv")[0]
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_moe_grouped_product_compiles_for_v5e(v5e):
    """The grouped matrix product of the expert layer at Trinity-Mini's
    widths, 16 experts of 2048 -> 1024 held: forward, the rows' and the
    weights' backward products, over a buffer of 8192 tokens' picks."""
    from tpuframe.ops import moe

    held, h, i, tile = 16, 2048, 1024, moe.TILE_ROWS
    n_tiles, _ = moe.buffer_tiles(8192, 8, held, 128, 2.0)
    x = jax.ShapeDtypeStruct((n_tiles * tile, h), jnp.bfloat16, sharding=v5e)
    w_in = jax.ShapeDtypeStruct((held, h, 2 * i), jnp.bfloat16, sharding=v5e)
    w_out = jax.ShapeDtypeStruct((held, i, h), jnp.bfloat16, sharding=v5e)
    te = jax.ShapeDtypeStruct((n_tiles,), jnp.int32, sharding=v5e)
    used = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=v5e)

    def loss(x, w_in, w_out, te, used):
        hid = moe.gmm(x, w_in, te, used, tile, False)
        return moe.gmm(hid[:, :i] * hid[:, i:], w_out, te, used, tile,
                       False).astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, w_in, w_out, te, used).compile()
    # the last forward product is dead under grad-of-sum: five calls left
    assert c.as_text().count("tpu_custom_call") >= 5


def test_flash_row_stats_are_lane_major_for_v5e(v5e):
    """The layout the chip takes (PERF.md §12.2): [bn, 1, s] residuals, not
    the [bn, s, 1] one that pads 128x in HBM."""
    from tpuframe.ops import flash_attention as fa

    assert fa._lse_lane_major()
    q = jax.ShapeDtypeStruct((8, 2048, 12, 64), jnp.bfloat16, sharding=v5e)
    txt = jax.jit(lambda q: fa.flash_mha_lse(
        q, q, q, causal=True, interpret=False)).lower(q).as_text()
    assert "96x1x2048xf32" in txt and "96x2048x1xf32" not in txt


@pytest.mark.parametrize("h,w,k,c_out", [
    (56, 56, 256, 128),    # most rows: layer2's first conv1
    (14, 14, 1024, 512),   # layer4's first conv1: the first tiling of this
                           # shape overflowed the real v5e's VMEM
    (7, 7, 2048, 512),     # deepest K: layer4's later conv1s
])
def test_fused_conv_bn_bwd_compiles_for_v5e(v5e, h, w, k, c_out):
    """ResNet-50 1x1 convs at b256 that ``supported()`` admits, from the
    most rows to the heaviest weight block and accumulator in VMEM: the
    guard that ``_pick_tiles``/``_vmem_est`` keep each inside it.  Layer4's
    1024 -> 2048 ``supported()`` refuses."""
    from tpuframe.ops import fused_conv_bn as fcb

    b = 256
    assert fcb.supported(h, w, b, k, c_out)
    assert not fcb.supported(7, 7, b, 1024, 2048)
    a = jax.ShapeDtypeStruct((b, h, w, k), jnp.bfloat16, sharding=v5e)
    wt = jax.ShapeDtypeStruct((k, c_out), jnp.float32, sharding=v5e)
    g = jax.ShapeDtypeStruct((c_out,), jnp.float32, sharding=v5e)
    cfg = (1e-5, fcb.DEFAULT_BLOCK_ROWS, False)  # interpret=False -> Mosaic

    def loss(a, w, gamma, beta):
        y, _, _ = fcb.conv1x1_bn_train(cfg, a, w, gamma, beta)
        return y.astype(jnp.float32).sum()

    _compile_grad(loss, a, wt, g, g)


@pytest.mark.parametrize("shape,dtype,mosaic", [
    ((64, 12, 64, 2048), jnp.bfloat16, True),    # the serving cell's rings
    ((128, 12, 64, 2048), jnp.float32, True),    # tiles of 8 rows, 128 slots
    ((4, 12, 64, 512), jnp.bfloat16, True),      # chip_smoke's engine
    ((4, 2, 128, 256), jnp.bfloat16, True),      # a head of whole lanes
    ((4, 12, 64, 512), jnp.bfloat16, False),     # the einsums, standing in
])
def test_decode_attention_compiles_for_v5e(v5e, monkeypatch, shape, dtype,
                                           mosaic):
    """Decode attention at the 124M width, bf16 and float32: the Mosaic
    kernel over the blocks the slots hold (one call for K and V, the rings
    left in HBM as they lie), or — where no Mosaic is asked for — the
    einsums; either way nothing ring-sized beside the rings."""
    from tpuframe.ops import attention as attn_ops

    if mosaic:
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "0")
    b, n, d, _ = shape
    q = jax.ShapeDtypeStruct((b, 1, n, d), dtype, sharding=v5e)
    kv = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    c = jax.jit(lambda q, k, v, n: attn_ops.decode_attention(
        q, k, v, lengths=n)).lower(q, kv, kv, lengths).compile()
    assert c.as_text().count("tpu_custom_call") == int(mosaic)
    ring_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    assert c.memory_analysis().temp_size_in_bytes < ring_bytes // 8


@pytest.mark.parametrize("shape,dtype", [
    ((64, 12, 64, 2048), jnp.bfloat16),   # the serving cell's ring
    ((64, 12, 64, 2048), jnp.float32),    # tiles of 8 rows, not 16
    ((8, 4, 16, 128), jnp.float32),       # tiny-lm: 4 heads of 16, one block
    ((4, 4, 16, 256), jnp.bfloat16),      # a head of one bf16 tile
    ((200, 576, 1024), jnp.bfloat16),     # a latent's row, slots past 128
])
def test_ring_store_compiles_for_v5e(v5e, shape, dtype):
    """Mosaic takes the store across the rings it has to serve — rows of
    any leading dimensions, 16- and 32-bit — and writes in place."""
    from tpuframe.ops import ring_store as rs

    ring = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    rows = jax.ShapeDtypeStruct(shape[:-1], dtype, sharding=v5e)
    idx = jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=v5e)
    assert rs.supported(ring, rows)
    c = jax.jit(lambda r, x, i: rs.ring_store(r, x, i, interpret=False),
                donate_argnums=0).lower(ring, rows, idx).compile()
    assert c.as_text().count("tpu_custom_call") == 1
    m = c.memory_analysis()
    ring_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    assert m.alias_size_in_bytes == ring_bytes
    assert m.temp_size_in_bytes < ring_bytes // 8


@pytest.fixture(scope="module")
def serve_decode_program(v5e):
    """The serving cell's decode program (64 slots, ring 2048, 12 layers of
    12 x 64, vocabulary 50257, bf16), compiled for the described chip:
    ``(compiled, its text, the cache's spec, the model's config)``."""
    from tpuframe.models.transformer_lm import LMConfig, TransformerLM
    from tpuframe.serve import engine as engine_lib
    from tpuframe.serve import kv_cache as kv

    cfg = LMConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                   num_heads=12, intermediate_size=3072, max_seq=2048,
                   dtype="bfloat16")
    model = TransformerLM(cfg)
    spec = kv.spec_for_model(cfg, slots=64, capacity=2048)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          variables["params"])
    ring = sds(spec.layer_shape(), jnp.dtype(spec.dtype))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPUFRAME_PALLAS_INTERPRET", "0")   # lower Mosaic
        mp.setenv("TPUFRAME_TUNE_GEN", "v5e")
        mp.setenv("TPUFRAME_TUNE_DB", "off")
        c = jax.jit(engine_lib.make_decode_fn(model),
                    donate_argnums=(1, 2, 3)).lower(
            params, sds((64, 1), jnp.int32), sds((64,), jnp.int32),
            ((ring, ring),) * cfg.num_layers).compile()
    return c, c.as_text(), spec, cfg


def _kernel_calls(text, name):
    """The program's Mosaic calls whose kernel is ``name``."""
    return re.findall(rf"^\s*%{name}[.\d]* = .*custom-call\(.*"
                      r'custom_call_target="tpu_custom_call"', text,
                      flags=re.M)


def test_serve_decode_step_stores_in_one_pass_for_v5e(serve_decode_program):
    """The serving cell's decode program: the KV store is 24 kernel calls
    on the donated rings — no per-slot ``while`` loop (a scatter's
    expansion: 24 loops of 64 iterations before PR 30), no scatter, no
    ring-sized copy or temporary."""
    c, text, spec, cfg = serve_decode_program
    assert len(_kernel_calls(text, "ring_store")) == 2 * cfg.num_layers
    assert " while(" not in text and " scatter(" not in text
    ring_elems = math.prod(spec.layer_shape())
    for line in text.splitlines():
        found = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if found:
            elems = math.prod(int(d) for d in found.group(1).split(","))
            assert elems < ring_elems, f"a ring-sized copy: {line.strip()}"
    m = c.memory_analysis()
    assert spec.total_bytes() == 4_831_838_208
    assert m.alias_size_in_bytes >= spec.total_bytes()
    assert m.temp_size_in_bytes < 64 << 20


def test_serve_decode_step_attends_over_held_blocks_for_v5e(
        serve_decode_program):
    """The same program's attention: 12 kernel calls, one a layer for K
    and V both, that take the rings as the stores leave them — and no
    fusion left that makes ``[64, 12, 1, 2048]`` scores, which is what
    reading every column of every ring looked like (24 loop fusions of
    268 us before PR 32).  Every Mosaic call of the program is one of the
    two kernels."""
    c, text, spec, cfg = serve_decode_program
    calls = _kernel_calls(text, "decode_attention")
    assert len(calls) == cfg.num_layers
    for call in calls:
        assert len(re.findall(r"%ring_store[.\d]*", call)) == 2, call
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 3 * cfg.num_layers
    scores = re.compile(r"\[64,12,1,2048\]|\[64,12,2048\]")
    assert not [line for line in text.splitlines() if scores.search(line)]
