"""The ``afmoe`` model (models/afmoe.py), its dropless expert layer
(ops/moe.py) and the windowed, grouped-head flash kernels, at toy widths
that keep Trinity-Mini's ratios, against the benchmark's plain reference
(benchmark/reference/trinity_mini.py) and against plain compositions."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = "sliding_attention", "full_attention"
ARCH = dict(vocab_size=256, hidden_size=32, num_layers=5, num_heads=4,
            num_kv_heads=2, head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            num_shared_experts=1, num_dense_layers=1,
            layer_types=[SLIDING] * 4 + [FULL], sliding_window=16,
            rope_theta=10000.0, rms_norm_eps=1e-5, route_norm=True,
            route_scale=2.826, experts_held=4, expert_first=0, max_seq=64)
JOB = dict(optimizer="adamw", base_lr=3e-3, scale_lr_by_batch=False,
           schedule="cosine", warmup_steps=2, total_steps=50,
           weight_decay=0.1, grad_clip_norm=1.0, global_batch=8)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "trinity_mini.py")
    spec = importlib.util.spec_from_file_location("ref_trinity_mini", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(k.key) for k in path) for path, _ in flat], \
        [leaf for _, leaf in flat]


@pytest.fixture(scope="module")
def followed(ref):
    """Three AdamW steps of the program through ``build_harness`` (a row
    of the batch on each of the 8 virtual devices) and of the reference,
    from the reference's seeded weights."""
    from tpuframe.parallel.mesh import MeshSpec
    from tpuframe.train import build_harness
    from tpuframe.utils.config import TrainConfig

    kwargs = {k: v for k, v in ARCH.items()}
    cfg = TrainConfig(
        name="afmoe_toy", model="afmoe",
        model_kwargs=dict(kwargs, attn_impl="pallas", remat=True),
        dataset="lm_text", dataset_kwargs=dict(seq_len=64, vocab_size=256,
                                               synthetic_size=16),
        fused_xent=True, mesh=MeshSpec(data=-1), seed=5, **JOB)
    h = build_harness(cfg)
    try:
        weights = ref.init_weights(ARCH, 11)
        names_w, leaves_w = _paths(weights["params"])
        names_p, leaves_p = _paths(h.state.params)
        assert names_w == names_p
        assert [a.shape for a in leaves_w] == [b.shape for b in leaves_p]
        assert jax.tree.structure(weights["model_state"]) == \
            jax.tree.structure(h.state.model_state)
        place = lambda w, old: jax.device_put(w, old.sharding)  # noqa: E731
        state = dataclasses.replace(
            h.state,
            params=jax.tree.map(place, weights["params"], h.state.params),
            model_state=jax.tree.map(place, weights["model_state"],
                                     h.state.model_state))
        it = iter(h.train_loader)
        batches, losses, grad = [], [], None
        for i in range(3):
            batch = next(it)
            batches.append({k: jnp.asarray(np.asarray(v))
                            for k, v in batch.items()})
            state, metrics = h.train_step(state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:
                mu = [x for x in jax.tree.leaves(
                    state.opt_state, is_leaf=lambda x: jax.tree.structure(x)
                    == jax.tree.structure(state.params))
                    if jax.tree.structure(x)
                    == jax.tree.structure(state.params)][0]
                grad = jax.tree.map(lambda m: np.asarray(m) / (1 - ref.B1),
                                    mu)
        from tpuframe.obs import metrics as obs_metrics

        counters = obs_metrics.counters("moe.")
        params = jax.tree.map(np.asarray, state.params)
    finally:
        h.train_loader.close()
        h.eval_loader.close()
    start = ref.init_weights(ARCH, 11)["params"]
    out = ref.train_steps(ARCH, JOB, start, batches)
    return dict(names=names_p, losses=losses, grad=grad, params=params,
                start=jax.tree.map(np.asarray, start), ref=out,
                counters=counters)


@pytest.mark.parametrize("what", ["loss", "gradient", "adamw3", "bias"])
def test_program_follows_reference(followed, what):
    f = followed
    if what == "loss":
        np.testing.assert_allclose(f["losses"], f["ref"]["losses"],
                                   rtol=2e-5)
        return
    if what == "gradient":
        got, want = f["grad"], f["ref"]["opt_grad"]
    else:
        got = jax.tree.map(lambda a, b: a - b, f["params"], f["start"])
        want = f["ref"]["delta"]
    for name, a, b in zip(f["names"], jax.tree.leaves(got),
                          jax.tree.leaves(want)):
        if what == "bias":
            if name.endswith("router/bias"):   # selects; is never moved
                assert not np.any(np.asarray(a)), name
            continue
        scale = max(float(np.max(np.abs(b))), 1e-12)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-3,
                                   err_msg=name)


def test_counters_add_up_without_a_sync_a_step(followed):
    c = followed["counters"]    # a replica's own: the mean over the 8
    picks = 4 * 3 * 64 * 2      # expert layers x steps x tokens x k
    assert c["moe.tokens_routed"] == picks
    assert abs(c["moe.rows_here"] + c["moe.rows_absent"] - picks) <= 1
    assert 0 < c["moe.rows_here"] < picks
    assert c["moe.rows_looped"] == 0 and c["moe.layers"] == 4
    assert abs(sum(c[f"moe.load.{e}"] for e in range(8)) - picks // 4) <= 8


@pytest.mark.parametrize("reader", ["no_prefix", "another_prefix",
                                    "moe_prefix", "donated_state"])
def test_counters_leave_the_device_only_when_asked_by_name(reader,
                                                           monkeypatch):
    """The flight recorder's dump, the exporter's scrape and ``run_end``
    read ``counters()`` whole, from threads and at moments at which a
    device transfer may block or find a donated buffer."""
    from tpuframe.models import afmoe
    from tpuframe.obs import metrics as obs_metrics

    fetched = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (fetched.append(1), get(x))[1])
    load = jnp.arange(8, dtype=jnp.float32)
    rows = jnp.float32(5)
    obs_metrics.reset_counters("moe.")
    try:
        afmoe.Afmoe.publish_state({"moe_counters": {"block_1": {
            "load": load, "rows_here": rows}}})
        obs_metrics.bump("loader.batches")
        if reader == "no_prefix":
            got = obs_metrics.counters()
            assert "loader.batches" in got
        elif reader == "another_prefix":
            got = obs_metrics.counters("loader.")
        elif reader == "donated_state":
            rows.delete()
            got = obs_metrics.counters("moe.")
        else:
            got = obs_metrics.counters("moe.")
            assert got["moe.rows_here"] == 5 and got["moe.load.7"] == 7
        if reader in ("no_prefix", "another_prefix"):
            assert not fetched
        if reader != "moe_prefix":
            assert not any(k.startswith("moe.") for k in got)
    finally:
        obs_metrics.reset_counters("moe.")
        afmoe._latest[0] = None


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all shares, and the shared expert once, are the
    uncut layer: in the reference and in the program's dispatch."""
    from tpuframe.ops import moe

    full = dict(ARCH, experts_held=8)
    p = ref.init_weights(full, 3)["params"]["block_2"]
    m = jax.random.normal(jax.random.key(0), (96, 32))
    idx, w = ref.route(full, p, m, None)
    whole = ref.routed(full, p, m, idx, w, None)
    ex = p["moe"]["experts"]
    parts_ref, parts_prog = 0, 0
    for first in (0, 4):
        share = dict(ARCH, experts_held=4, expert_first=first)
        ps = dict(p, moe=dict(p["moe"], experts={
            k: v[first:first + 4] for k, v in ex.items()}))
        parts_ref += ref.routed(share, ps, m, idx, w, None)
        sl = ps["moe"]["experts"]
        y, plan = moe.routed_experts(m, idx, w, sl["gate"], sl["up"],
                                     sl["down"], first=first, num_experts=8)
        parts_prog += y
        assert bool(plan.fits)
    np.testing.assert_allclose(parts_ref, whole, atol=1e-5)
    np.testing.assert_allclose(parts_prog, whole, atol=1e-5)


def _loop(x, idx, w, gate, up, down, first):
    y = 0
    for e in range(gate.shape[0]):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = y + we[:, None] * ((jax.nn.silu(x @ gate[e]) * (x @ up[e]))
                               @ down[e])
    return y


@pytest.mark.parametrize("case", ["random", "one_expert_takes_all",
                                  "overflow_takes_the_loop", "mosaic_kernel"])
def test_dispatch_against_a_loop_over_experts(case, monkeypatch):
    from tpuframe.ops import moe

    t, h, i, e, k, held, first = 96, 32, 16, 8, 2, 4, 2
    ks = jax.random.split(jax.random.key(1), 7)
    x = jax.random.normal(ks[0], (t, h))
    wr = jax.random.normal(ks[1], (h, e))
    bias = jnp.zeros((e,)).at[3].set(100.0 if case.startswith("one") else 0.0)
    gate, up = (jax.random.normal(kk, (held, h, i)) / 5 for kk in ks[2:4])
    down = jax.random.normal(ks[4], (held, i, h)) / 4
    factor = 0.25 if case.startswith("overflow") else 2.0
    if case == "mosaic_kernel":     # the kernels themselves, interpreted
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    seen = {}

    def prog(x, wr, gate, up, down):
        idx, w = moe.route_sigmoid_topk(x @ wr, bias, k=k, scale=2.0)
        y, plan = moe.routed_experts(x, idx, w, gate, up, down, first=first,
                                     num_experts=e, capacity_factor=factor,
                                     tile=8)
        seen.update(counts=plan.counts, fits=plan.fits)
        return y

    def plain(x, wr, gate, up, down):
        idx, w = moe.route_sigmoid_topk(x @ wr, bias, k=k, scale=2.0)
        return _loop(x, idx, w, gate, up, down, first)

    args = (x, wr, gate, up, down)
    np.testing.assert_allclose(prog(*args), plain(*args), atol=1e-5)
    assert bool(seen["fits"]) == (not case.startswith("overflow"))
    if case.startswith("one"):      # no row dropped: expert 3 has them all
        assert int(seen["counts"][3 - first]) == t
    co = jax.random.normal(ks[5], (t, h))
    got = jax.grad(lambda *a: jnp.sum(prog(*a) * co), range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * co), range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_bias_selects_and_does_not_weigh():
    from tpuframe.ops import moe
    from tpuframe.utils.optim import _decay_mask

    logits = jax.random.normal(jax.random.key(2), (64, 8))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    idx, w = moe.route_sigmoid_topk(logits, bias, k=2, scale=2.826)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))     # it selects
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, idx, axis=-1)        # and does not weigh
    np.testing.assert_allclose(
        w, 2.826 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    g = jax.grad(lambda b: moe.route_sigmoid_topk(logits, b, k=2)[1].sum())(
        bias)
    assert not np.any(np.asarray(g))
    mask = _decay_mask({"moe": {"router": {"bias": 0, "kernel": 0},
                                "experts": {"gate": 0}}})
    assert mask == {"moe": {"router": {"bias": False, "kernel": True},
                            "experts": {"gate": True}}}


@pytest.mark.parametrize("window", [True, False], ids=["window", "full"])
def test_rotary_on_window_layers_only(window):
    """Stretching the positions moves a window layer's attention and leaves
    a full layer's where it was: full layers carry no positions at all."""
    from tpuframe.models.afmoe import AfmoeConfig, GatedAttention

    attn = GatedAttention(AfmoeConfig.tiny(), window)
    x = jax.random.normal(jax.random.key(0), (1, 64, 32))
    pos = jnp.arange(64)
    params = attn.init(jax.random.key(1), x, pos)
    a, b = attn.apply(params, x, pos), attn.apply(params, x, 2 * pos)
    assert bool(jnp.allclose(a, b, atol=1e-6)) != window


@pytest.mark.parametrize("scores", ["router", "skewed"])
def test_calibration_balances_the_load(ref, scores):
    cfg = dict(num_experts=32, num_experts_per_tok=4)
    key = jax.random.key(4)
    if scores == "router":      # a random router over normed activations
        x = jax.random.normal(key, (4096, 64))
        s = jax.nn.sigmoid(x @ jax.random.normal(jax.random.key(5), (64, 32))
                           / 8 + jax.random.normal(jax.random.key(6), (32,)))
    else:                       # some experts liked three times the others
        s = jax.nn.sigmoid(jax.random.normal(key, (4096, 32))) \
            * jnp.linspace(0.5, 1.5, 32)[None, :]

    def worst(b):
        _, idx = jax.lax.top_k(s + b, 4)
        load = np.bincount(np.asarray(idx).ravel(), minlength=32)
        return load.max() / load.mean()

    assert worst(jnp.zeros((32,))) > 1.1
    assert worst(jax.jit(lambda s: ref.balance_bias(cfg, s))(s)) <= 1.1


@pytest.mark.parametrize("schedule,warm", [("cosine", 2000), ("cosine", 0),
                                           ("constant", 10)])
def test_reference_schedule_is_the_programs(ref, schedule, warm):
    from tpuframe.utils.config import TrainConfig
    from tpuframe.utils.optim import lr_schedule

    job = dict(base_lr=3e-4, scale_lr_by_batch=False, schedule=schedule,
               warmup_steps=warm, total_steps=100000, global_batch=1)
    sched = lr_schedule(TrainConfig(name="t", model="afmoe", **job),
                        1 / 256.0)
    for step in (0, 1, 2, 299, warm, warm + 1, 50000, 99999):
        np.testing.assert_allclose(
            float(ref.learning_rate(job, jnp.float32(step))),
            float(sched(step)), rtol=2e-5, atol=1e-12)   # float32 ramps


# -- the flash kernels: a window, and K/V heads shared by a group -----------


def _masked_einsum(q, k, v, window):
    s, d = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = i >= j
    if window:
        keep = keep & (i - j < window)
    pr = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", pr, v)


@pytest.mark.parametrize("s,n,n_kv,d,window,bq,bk", [
    (64, 4, 2, 16, 16, None, None),      # the toy block's attention
    (512, 4, 2, 64, 200, 128, 128),      # K/V in blocks, a ragged window
    (512, 2, 1, 64, 64, 128, 256),       # a window narrower than a block
    (512, 4, 1, 64, None, 256, None),    # grouped heads, no window
])
def test_flash_window_grouped_heads(s, n, n_kv, d, window, bq, bk):
    from tpuframe.ops import flash_attention as fa

    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (2, s, n, d))
    k = jax.random.normal(ks[1], (2, s, n_kv, d))
    v = jax.random.normal(ks[2], (2, s, n_kv, d))
    co = jax.random.normal(ks[3], (2, s, n, d))
    kern = lambda q, k, v: fa.flash_mha(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(kern(q, k, v),
                               _masked_einsum(q, k, v, window), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kern(*a) * co), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_masked_einsum(*a, window) * co),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("what", ["window_past_the_sequence",
                                  "group_of_one"])
def test_flash_without_window_or_group_is_the_kernel_it_was(what):
    """A window that reaches past the sequence and K/V heads repeated by
    hand take the very program a plain causal call takes: bit-equal
    outputs and query gradients (dK and dV of a group sum in another
    order, so they are close)."""
    from tpuframe.ops import flash_attention as fa

    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32))
    k, v = (jax.random.normal(kk, (1, 256, 2, 32)) for kk in ks[1:])
    rep = lambda t: jnp.repeat(t, 2, axis=2)  # noqa: E731
    plain = lambda q, k, v: fa.flash_mha(  # noqa: E731
        q, rep(k), rep(v), causal=True, block_q=128, block_k=128,
        interpret=True)
    if what == "group_of_one":
        other = lambda q, k, v: fa.flash_mha(  # noqa: E731
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    else:
        other = lambda q, k, v: fa.flash_mha(  # noqa: E731
            q, rep(k), rep(v), causal=True, window=256, block_q=128,
            block_k=128, interpret=True)
    assert bool(jnp.array_equal(plain(q, k, v), other(q, k, v)))
    ga = jax.grad(lambda *a: plain(*a).sum(), (0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: other(*a).sum(), (0, 1, 2))(q, k, v)
    assert bool(jnp.array_equal(ga[0], gb[0]))
    for a, b in zip(ga[1:], gb[1:]):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_xla_attention_takes_window_and_grouped_heads():
    from tpuframe.ops.attention import multihead_attention

    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 8))
    k, v = (jax.random.normal(kk, (2, 64, 2, 8)) for kk in ks[1:])
    np.testing.assert_allclose(
        multihead_attention(q, k, v, causal=True, window=16, impl="xla"),
        _masked_einsum(q, k, v, 16), atol=1e-5)
    with pytest.raises(ValueError):
        multihead_attention(q, k, v, window=16, impl="xla")


def test_reference_window_layers_read_only_the_keys_in_reach(ref, monkeypatch):
    """With blocks of 32 queries a window layer's block is scored against
    a stretch of 64 keys, not all 256: the same attention."""
    cfg = dict(ARCH, max_seq=256)
    a = ref.init_weights(cfg, 2)["params"]["block_1"]["attn"]
    h = jax.random.normal(jax.random.key(7), (2, 256, 32))
    whole = ref._attention(cfg, a, h, 16, None)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    np.testing.assert_allclose(ref._attention(cfg, a, h, 16, None), whole,
                               atol=1e-6)


def test_plain_causal_call_traces_to_the_kernels_it_had(monkeypatch):
    """The 124M LM's flash call (no window, one query head a K/V head)
    traces, forward and backward, to the same jaxpr — kernels, grids, block
    specs and all — as on the commit before the window and the grouped
    heads came (38ab1d4): its hash, taken there, under this repo's one jax.
    A change that means to touch that program takes the hash anew."""
    import hashlib
    import re

    from tpuframe.ops import flash_attention as fa

    monkeypatch.setenv("TPUFRAME_TUNE_GEN", "v5e")
    monkeypatch.setenv("TPUFRAME_TUNE_DB", "off")
    q = jax.ShapeDtypeStruct((8, 2048, 12, 64), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_mha(q, k, v, causal=True, interpret=False)
        .astype(jnp.float32).sum(), (0, 1, 2)))(q, q, q))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "e2727861fc587a88bea9e05c982bde5f72cbb0b1a458c49f287a15a25c0b08eb")
