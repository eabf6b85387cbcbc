"""Flash-attention kernel vs the XLA einsum reference (SURVEY.md §7 test
strategy: unit tests per module on CPU jax — the Pallas interpreter executes
the very kernel that compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.ops import attention
from tpuframe.ops import flash_attention as fa


def _qkv(b=2, s=256, n=4, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, n, d)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


def _padding_mask(b=2, s=256, seed=1):
    lengths = jax.random.randint(jax.random.key(seed), (b,), s // 4, s + 1)
    return (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.int32)


def test_forward_matches_xla():
    q, k, v = _qkv()
    got = fa.flash_mha(q, k, v, interpret=True)
    want = attention._xla_attention(q, k, v, mask=None, dropout_rate=0.0,
                                    dropout_rng=None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_padding_mask():
    q, k, v = _qkv()
    mask = _padding_mask()
    got = fa.flash_mha(q, k, v, mask=mask, interpret=True)
    want = attention._xla_attention(q, k, v, mask=mask, dropout_rate=0.0,
                                    dropout_rng=None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_causal():
    q, k, v = _qkv(s=256)
    got = fa.flash_mha(q, k, v, causal=True, interpret=True)
    s = q.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    want = attention._xla_attention(q, k, v, mask=causal, dropout_rate=0.0,
                                    dropout_rng=None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_multi_block_seq():
    # 2 q-blocks x 2 kv-blocks exercises the online-softmax accumulation.
    q, k, v = _qkv(s=256)
    got = fa.flash_mha(q, k, v, block_q=128, block_k=128, interpret=True)
    want = attention._xla_attention(q, k, v, mask=None, dropout_rate=0.0,
                                    dropout_rng=None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_xla(causal):
    q, k, v = _qkv(b=1, s=256, n=2, d=64)
    mask = None if causal else _padding_mask(b=1, s=256)

    def loss_flash(q, k, v):
        o = fa.flash_mha(q, k, v, mask=mask, causal=causal, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_xla(q, k, v):
        m = mask
        if causal:
            s = q.shape[1]
            m = jnp.tril(jnp.ones((s, s), bool))[None, None]
        o = attention._xla_attention(q, k, v, mask=m, dropout_rate=0.0,
                                     dropout_rng=None)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for gf, gx, name in zip(g_flash, g_xla, "qkv"):
        np.testing.assert_allclose(gf, gx, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16, s=128)
    got = fa.flash_mha(q, k, v, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = attention._xla_attention(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32), mask=None,
                                    dropout_rate=0.0, dropout_rng=None)
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=3e-2, rtol=3e-2)


def test_dispatch_selects_pallas(monkeypatch):
    q, k, v = _qkv(b=1, s=128, n=2, d=64)
    calls = []
    real = fa.flash_mha
    monkeypatch.setattr(fa, "flash_mha",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    out = attention.multihead_attention(q, k, v, impl="pallas")
    assert calls, "dispatch silently fell back to the XLA path"
    want = attention.multihead_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_unsupported_shape_falls_back():
    # seq 100 doesn't tile; dispatch must silently use the XLA path.
    q, k, v = _qkv(b=1, s=100, n=2, d=64)
    out = attention.multihead_attention(q, k, v, impl="pallas")
    want = attention.multihead_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    assert not fa.supported(q)


def test_cross_attention_kv_shape_guard():
    # s_kv=200 doesn't tile into 128-blocks: supported() must reject it and
    # flash_mha must refuse rather than silently truncating keys.
    q, _, _ = _qkv(b=1, s=128, n=2, d=64)
    k = jnp.ones((1, 200, 2, 64), jnp.float32)
    v = jnp.ones((1, 200, 2, 64), jnp.float32)
    assert not fa.supported(q, k)
    with pytest.raises(ValueError, match="do not tile"):
        fa.flash_mha(q, k, v, interpret=True)


def test_fully_masked_row_zero_grads():
    # A zero-length (all-padding) batch row: output and all grads must be
    # exactly zero for it — not s_kv-inflated garbage.
    q, k, v = _qkv(b=2, s=128, n=2, d=64)
    mask = jnp.stack([jnp.zeros(128, jnp.int32), jnp.ones(128, jnp.int32)])

    out = fa.flash_mha(q, k, v, mask=mask, interpret=True)
    np.testing.assert_array_equal(out[0], jnp.zeros_like(out[0]))

    def loss(q, k, v):
        o = fa.flash_mha(q, k, v, mask=mask, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, name in ((dq, "dq"), (dk, "dk"), (dv, "dv")):
        np.testing.assert_array_equal(
            g[0], jnp.zeros_like(g[0]), err_msg=f"{name}[masked row]")
        assert float(jnp.max(jnp.abs(g[1]))) > 0  # live row still flows


def test_precision_argument_plumbs_through(monkeypatch):
    """precision reaches EVERY dot in fwd and bwd — asserted structurally
    by spying on lax.dot_general at trace time (the interpreter's numerics
    can't distinguish precisions, so allclose alone would pass even if the
    kwarg were dropped from the kernels)."""
    flash_mha = fa.flash_mha
    recorded = []
    orig_dot = jax.lax.dot_general

    def spy(*a, **k):
        recorded.append(k.get("precision"))
        return orig_dot(*a, **k)

    rng = np.random.default_rng(3)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(0, 0.5, size=(1, 64, 2, 16)), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def loss(f):
        def g(q, k, v):
            return jnp.sum(f(q, k, v) ** 2)
        return jax.grad(g, argnums=(0, 1, 2))(q, k, v)

    base = flash_mha(q, k, v, causal=True)
    g_base = loss(lambda q, k, v: flash_mha(q, k, v, causal=True))

    monkeypatch.setattr(jax.lax, "dot_general", spy)
    hi = flash_mha(q, k, v, causal=True, precision=jax.lax.Precision.HIGHEST)
    g_hi = loss(lambda q, k, v: flash_mha(
        q, k, v, causal=True, precision=jax.lax.Precision.HIGHEST))
    monkeypatch.undo()

    # structural: every kernel dot (fwd scores+accum, bwd recompute/dp/dq/
    # dkv) was traced with the requested precision
    assert len(recorded) >= 6, recorded
    assert all(p == jax.lax.Precision.HIGHEST for p in recorded), recorded
    # interpreter numerics are precision-invariant: values must match
    np.testing.assert_allclose(np.asarray(base), np.asarray(hi),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(g_base, g_hi):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Generation-conditional lse/delta layout (PERF.md §12.2): lane-major
# residuals for every generation newer than v4; sublane-major for v4 and
# unknown targets (the layout every generation can compile).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gen,lane", [
    (None, False),   # unknown target (CPU tier-1 runs) -> conservative
    ("v4", False),   # tpu.dynamic_gather unsupported -> sublane-major
    ("v5e", True),
    ("v5p", True),
    ("v6e", True),
])
def test_lse_layout_pinned_per_generation(monkeypatch, gen, lane):
    monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
    if gen is not None:
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", gen)
    assert fa._lse_lane_major() is lane


@pytest.mark.parametrize("gen", [None, "v5e"])
def test_lse_layout_residual_shape(monkeypatch, gen):
    # the layout decision is visible in the residual the fwd pass saves:
    # [bn, s] either way at the jax level, but built from a lane-major
    # [bn, 1, s] or sublane-major [bn, s, 1] HBM array.
    monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
    if gen is not None:
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", gen)
    q, k, v = _qkv(b=1, s=128, n=2, d=64)
    qf = q.reshape(2, 128, 64)
    out, lse = fa._flash_fwd(qf, k.reshape(2, 128, 64),
                             v.reshape(2, 128, 64), None, scale=64 ** -0.5,
                             causal=False, tiles=fa.Tiles(64, 64, 64),
                             lane=fa._lse_lane_major(), interpret=True)
    assert lse.shape == (2, 128)
    assert out.shape == qf.shape


def test_lse_layouts_numerically_equivalent(monkeypatch):
    # the re-layout is a pure storage decision: fwd outputs, the saved
    # lse, and all three input grads must be identical under both
    # layouts (same blocks, same accumulation order).
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(0, 0.5, size=(4, 128, 64)), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def run(gen):
        monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
        if gen is not None:
            monkeypatch.setenv("TPUFRAME_TUNE_GEN", gen)
        t = fa.Tiles(64, 64, 64)
        out, lse = fa._flash_fwd(q, k, v, None, scale=64 ** -0.5,
                                 causal=True, tiles=t, interpret=True,
                                 lane=fa._lse_lane_major())
        dq, dk, dv = fa._flash_bwd(q, k, v, None, out, lse, 2 * out,
                                   scale=64 ** -0.5, causal=True,
                                   tiling=(t, t, t), interpret=True)
        return out, lse, dq, dk, dv

    sub = run(None)      # sublane-major
    lan = run("v5e")     # lane-major
    for a, b, name in zip(sub, lan, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


# ---------------------------------------------------------------------------
# Tiling chosen from the shape (PR 26): the rule, the kernels at its blocks
# against 128 x 128, and the record of what engaged.
# ---------------------------------------------------------------------------

_SEQS = (128, 512, 640, 1152, 2048, 8192, 32768)


@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("s_q", _SEQS)
def test_block_rule_tiles_every_shape_inside_its_budget(s_q, d):
    for s_kv in _SEQS:
        for itemsize in (2, 4):
            for kernel in ("fwd", "dq", "dkv"):
                t = fa.choose_tiles(kernel, s_q, s_kv, d, itemsize)
                for block, seq in ((t.block_q, s_q), (t.block_k, s_kv)):
                    assert seq % block == 0, (kernel, t)
                    assert block % 128 == 0 or block == seq, (kernel, t)
                walked = t.block_q if kernel == "dkv" else t.block_k
                assert walked % t.sub == 0 and t.sub % 128 == 0, (kernel, t)
                assert fa.vmem_bytes(kernel, t, d, itemsize) \
                    <= fa.VMEM_BUDGET, (kernel, t)
        # whatever tiled at 128 x 128 still tiles
        q = jax.ShapeDtypeStruct((1, s_q, 2, d), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, s_kv, 2, d), jnp.bfloat16)
        assert fa.supported(q, k, 128, 128) and fa.supported(q, k)


@pytest.mark.parametrize("s,ok", [(64, True), (100, False), (192, False),
                                  (200, False), (640, True), (1152, True)])
def test_block_rule_keeps_what_supported_took(s, ok):
    q = jax.ShapeDtypeStruct((1, s, 2, 64), jnp.float32)
    assert fa.supported(q) is ok
    assert fa.supported(q, None, 128, 128) is ok


def _fwd_bwd(q, k, v, mask, causal, tiling):
    """out, lse, dq, dk, dv of the folded kernels at one tiling."""
    scale = q.shape[-1] ** -0.5
    out, lse = fa._flash_fwd(q, k, v, mask, scale=scale, causal=causal,
                             tiles=tiling[0], lane=fa._lse_lane_major(),
                             interpret=True)
    do = jnp.cos(out) + 0.5                    # any cotangent will do
    dlse = jnp.where(lse > fa.NEG_INF / 2, 0.25, 0.0)
    dq, dk, dv = fa._flash_bwd(q, k, v, mask, out, lse, do, scale=scale,
                               causal=causal, tiling=tiling, interpret=True,
                               dlse=dlse)
    return out, lse, dq, dk, dv


@pytest.mark.parametrize("gen", [None, "v5e"], ids=["sublane", "lane"])
@pytest.mark.parametrize("s_q,s_kv,causal,masked,tiling", [
    # K/V (Q/dO in dkv) resident, an inner loop of four that stops at the
    # diagonal: the LM cell's geometry at a size the interpreter affords
    (512, 512, True, False, ((256, 512, 128), (256, 512, 128),
                             (512, 256, 128))),
    # two grid-level K blocks (clamped index maps above the diagonal), two
    # sub-blocks in each, a key mask with one batch row fully masked
    (512, 1024, True, True, ((256, 512, 256), (128, 512, 256),
                             (256, 512, 128))),
    # more rows than keys, causal: the last q blocks see every key
    (1024, 512, True, False, ((512, 256, 128), (256, 512, 256),
                              (512, 128, 256))),
    # an encoder's shape: no causal mask, a padding mask, cross lengths
    (256, 512, False, True, ((256, 512, 128), (128, 256, 128),
                             (256, 256, 128))),
])
def test_kernels_at_large_tiles_equal_128x128(monkeypatch, s_q, s_kv, causal,
                                              masked, tiling, gen):
    monkeypatch.delenv("TPUFRAME_TUNE_GEN", raising=False)
    if gen is not None:
        monkeypatch.setenv("TPUFRAME_TUNE_GEN", gen)
    rng = np.random.default_rng(11)
    mk = lambda s: jnp.asarray(  # noqa: E731
        rng.normal(0, 0.5, size=(2, s, 64)), jnp.float32)
    q, k, v = mk(s_q), mk(s_kv), mk(s_kv)
    mask = None
    if masked:   # batch row 0: no key attends at all; row 1: a padded tail
        mask = jnp.stack([jnp.zeros(s_kv, jnp.int32),
                          (jnp.arange(s_kv) < s_kv - 72).astype(jnp.int32)])
    small = fa.Tiles(128, 128, 128)
    want = _fwd_bwd(q, k, v, mask, causal, (small, small, small))
    got = _fwd_bwd(q, k, v, mask, causal,
                   tuple(fa.Tiles(*t) for t in tiling))
    for a, b, name in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    if masked:   # the masked-row convention survives the tiling
        out, lse, dq, dk, dv = got
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0
        assert bool(jnp.all(lse[0] == fa.NEG_INF))
        for g in (dq, dk, dv):
            assert float(jnp.max(jnp.abs(g[0]))) == 0.0


@pytest.mark.parametrize("s,with_lse", [(640, False), (1024, True)])
def test_rule_choice_equals_128x128_through_the_public_api(s, with_lse):
    # 640 tiles only by 128 beside the whole sequence; 1024 takes the
    # rule's 512-row blocks with K/V resident.
    q, k, v = _qkv(b=1, s=s, n=2, d=64, seed=5)
    assert fa.choose_tiles("fwd", s, s, 64, 4)[:2] != (128, 128)

    def loss(blocks):
        def f(q, k, v):
            if with_lse:
                o, lse = fa.flash_mha_lse(q, k, v, causal=True,
                                          interpret=True, **blocks)
                return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
            return jnp.sum(fa.flash_mha(q, k, v, causal=True,
                                        interpret=True, **blocks) ** 2)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    (l_rule, g_rule) = loss({})
    (l_128, g_128) = loss(dict(block_q=128, block_k=128))
    np.testing.assert_allclose(l_rule, l_128, rtol=1e-5)
    for a, b, name in zip(g_rule, g_128, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5,
                                   atol=5e-5, err_msg=f"d{name}")


def test_kernel_impl_record_names_blocks_and_grid():
    from tpuframe.ops import kernel_impl

    kernel_impl.reset()
    try:
        q, k, v = _qkv(b=1, s=256, n=2, d=64)
        fa.flash_mha(q, k, v, causal=True)
        why = kernel_impl._resolved["flash_attention"]["interpret"]
        assert why == ("backend=cpu; fwd q256 k256 sub256 grid 2x1x1; "
                       "dq q256 k256 sub256 grid 2x1x1; "
                       "dkv q256 k256 sub256 grid 2x1x1")
        fa.flash_mha(q, k, v, causal=True, block_q=128, block_k=128)
        why = kernel_impl._resolved["flash_attention"]["interpret"]
        assert "fwd q128 k128 sub128 grid 2x2x2" in why
    finally:
        kernel_impl.reset()
