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
                             causal=False, block_q=64, block_k=64,
                             interpret=True)
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
        out, lse = fa._flash_fwd(q, k, v, None, scale=64 ** -0.5,
                                 causal=True, block_q=64, block_k=64,
                                 interpret=True)
        dq, dk, dv = fa._flash_bwd(q, k, v, None, out, lse, 2 * out,
                                   scale=64 ** -0.5, causal=True,
                                   block_q=64, block_k=64, interpret=True)
        return out, lse, dq, dk, dv

    sub = run(None)      # sublane-major
    lan = run("v5e")     # lane-major
    for a, b, name in zip(sub, lan, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
