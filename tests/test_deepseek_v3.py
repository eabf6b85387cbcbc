"""The ``deepseek_v3`` model (models/deepseek_v3.py) and the latent flash
kernels (ops/flash_attention.py: flash_mla), at toy widths that keep
Kanana-2-30B-A3B's ratios (qk 16 + 8, v 16, latent 32, 4 heads, 8 experts,
2 a token, 4 held, 2 shared, 1 dense + 2 expert layers), against the
benchmark's plain reference (benchmark/reference/kanana2_30b_a3b.py) and
against plain compositions."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = dict(vocab_size=256, hidden_size=48, num_layers=3, num_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=32, intermediate_size=64, moe_intermediate_size=8,
            num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
            num_dense_layers=1, rope_theta=1e6, rms_norm_eps=1e-6,
            route_norm=True, route_scale=2.448, experts_held=4,
            expert_first=0, max_seq=64)
JOB = dict(optimizer="adamw", base_lr=3e-3, scale_lr_by_batch=False,
           schedule="cosine", warmup_steps=2, total_steps=50,
           weight_decay=0.1, grad_clip_norm=1.0, global_batch=8)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "kanana2_30b_a3b.py")
    spec = importlib.util.spec_from_file_location("ref_kanana2", path)
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
    of the batch on each of the 8 virtual devices; the latent flash kernels
    interpreted, per-block remat, the fused head) and of the reference,
    from the reference's seeded weights."""
    from tpuframe.obs import metrics as obs_metrics
    from tpuframe.parallel.mesh import MeshSpec
    from tpuframe.train import build_harness
    from tpuframe.utils.config import TrainConfig

    cfg = TrainConfig(
        name="deepseek_v3_toy", model="deepseek_v3",
        model_kwargs=dict(ARCH, attn_impl="pallas", remat=True),
        dataset="lm_text", dataset_kwargs=dict(
            seq_len=64, vocab_size=256, synthetic_size=16, uniform_ids=True),
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
        whole = jax.tree.structure(state.params)
        for i in range(3):
            batch = next(it)
            batches.append({k: jnp.asarray(np.asarray(v))
                            for k, v in batch.items()})
            state, metrics = h.train_step(state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:
                mu = [x for x in jax.tree.leaves(
                    state.opt_state,
                    is_leaf=lambda x: jax.tree.structure(x) == whole)
                    if jax.tree.structure(x) == whole][0]
                grad = jax.tree.map(lambda m: np.asarray(m) / (1 - ref.B1),
                                    mu)
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


def test_counters_add_up(followed):
    c = followed["counters"]    # a replica's own: the mean over the 8
    picks = 2 * 3 * 64 * 2      # expert layers x steps x tokens x k
    assert c["moe.tokens_routed"] == picks
    assert abs(c["moe.rows_here"] + c["moe.rows_absent"] - picks) <= 1
    assert 0 < c["moe.rows_here"] < picks
    assert c["moe.rows_looped"] == 0 and c["moe.layers"] == 2


# -- the model against the reference, and what each part of it is worth ----


def _logits(impl, weights, ids):
    from tpuframe.models import get_model

    model = get_model("deepseek_v3", **dict(ARCH, attn_impl=impl))
    return model.apply({"params": weights["params"]}, ids)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_logits_are_the_references(ref, impl):
    weights = ref.init_weights(ARCH, 3)
    ids = jax.random.randint(jax.random.key(1), (2, 64), 0, 256)
    np.testing.assert_allclose(_logits(impl, weights, ids),
                               ref.forward(ARCH, weights["params"], ids),
                               atol=2e-5)


def _no_rope_on_the_shared_key(ref, cfg, params, patch):
    rope = ref._rope
    patch.setattr(ref, "_rope",
                  lambda x, theta: x if x.shape[2] == 1 else rope(x, theta))
    return cfg, params


def _no_latent_norm(ref, cfg, params, patch):
    rms = ref._rms
    patch.setattr(ref, "_rms", lambda cfg, x, scale: x * scale
                  if x.shape[-1] == cfg["kv_lora_rank"]
                  else rms(cfg, x, scale))
    return cfg, params


def _scale_of_the_nope_width(ref, cfg, params, patch):
    patch.setattr(ref, "score_scale",
                  lambda cfg: cfg["qk_nope_head_dim"] ** -0.5)
    return cfg, params


def _no_route_scale(ref, cfg, params, patch):
    return dict(cfg, route_scale=1.0), params


def _no_shared_experts(ref, cfg, params, patch):
    out = dict(params)
    for i in range(cfg["num_dense_layers"], cfg["num_layers"]):
        blk = params[f"block_{i}"]
        moe = dict(blk["moe"], shared=jax.tree.map(jnp.zeros_like,
                                                   blk["moe"]["shared"]))
        out[f"block_{i}"] = dict(blk, moe=moe)
    return cfg, out


@pytest.mark.parametrize("left_out", [
    _no_rope_on_the_shared_key, _no_latent_norm, _scale_of_the_nope_width,
    _no_route_scale, _no_shared_experts], ids=lambda f: f.__name__[1:])
def test_reference_without_a_part_is_not_the_program(ref, left_out,
                                                     monkeypatch):
    """The comparison above sees each part of the block: a reference that
    leaves one out lies a hundred tolerances from the program."""
    weights = ref.init_weights(ARCH, 3)
    ids = jax.random.randint(jax.random.key(1), (2, 64), 0, 256)
    got = _logits("xla", weights, ids)
    cfg, params = left_out(ref, ARCH, weights["params"], monkeypatch)
    gap = float(jnp.max(jnp.abs(got - ref.forward(cfg, params, ids))))
    assert gap > 100 * 2e-5, gap


def test_rotary_key_is_rotated_once_and_read_by_every_head(monkeypatch):
    """The attention op gets the rotary key as ``[B, S, 1, d_rope]``, never
    repeated; and a change to that one row's source moves every head."""
    from tpuframe.models.deepseek_v3 import DeepseekV3Config, LatentAttention
    from tpuframe.ops import attention as attn_ops

    seen = {}
    real = attn_ops.multihead_attention

    def spy(q, k, v, *, rope, **kw):
        seen.update(q=q.shape, k=k.shape, v=v.shape, q_rope=rope[0].shape,
                    k_rope=rope[1].shape)
        return real(q, k, v, rope=rope, **kw)

    monkeypatch.setattr(attn_ops, "multihead_attention", spy)
    c = DeepseekV3Config.tiny(hidden_size=64)   # Wo square: set to identity
    attn = LatentAttention(c)
    x = jax.random.normal(jax.random.key(0), (2, 64, 64))
    pos = jnp.arange(64)
    params = attn.init(jax.random.key(1), x, pos)
    assert seen == dict(q=(2, 64, 4, 16), k=(2, 64, 4, 16), v=(2, 64, 4, 16),
                        q_rope=(2, 64, 4, 8), k_rope=(2, 64, 1, 8))
    # the 8 columns of kv_a that make the rotary key, and only they
    bumped = jax.tree.map(lambda a: a, params)
    kernel = params["params"]["kv_a"]["kernel"]
    bumped["params"]["kv_a"]["kernel"] = kernel.at[:, 32:].add(0.5)
    out = params["params"]["out"]["kernel"]
    eye = dict(params["params"], out={"kernel": jnp.eye(*out.shape)})
    a = attn.apply({"params": eye}, x, pos)
    b = attn.apply({"params": dict(bumped["params"], out=eye["out"])}, x, pos)
    moved = jnp.abs(a - b).reshape(2, 64, 4, 16).max(axis=(0, 1, 3))
    assert bool(jnp.all(moved > 1e-3)), moved
    # positions reach the scores through the rope dims alone
    assert not bool(jnp.allclose(a, attn.apply({"params": eye}, x, 2 * pos),
                                 atol=1e-6))


@pytest.mark.parametrize("experts,held", [(8, 4), (16, 1)],
                         ids=["two_shares", "sixteen_shares"])
def test_shares_add_up_to_the_uncut_layer(ref, experts, held):
    """The routed parts of all shares, and the shared experts once, are the
    uncut layer: in the reference and in the program's dispatch."""
    from tpuframe.ops import moe

    arch = dict(ARCH, num_experts=experts)
    full = dict(arch, experts_held=experts)
    p = ref.init_weights(full, 3)["params"]["block_1"]
    m = jax.random.normal(jax.random.key(0), (96, 48))
    idx, w = ref.route(full, p, m, None)
    shared = ref._swiglu(p["moe"]["shared"], m, None)
    whole = ref.routed(full, p, m, idx, w, None) + shared
    ex = p["moe"]["experts"]
    parts_ref, parts_prog = shared, shared
    for first in range(0, experts, held):
        share = dict(arch, experts_held=held, expert_first=first)
        sl = {k: v[first:first + held] for k, v in ex.items()}
        ps = dict(p, moe=dict(p["moe"], experts=sl))
        parts_ref += ref.routed(share, ps, m, idx, w, None)
        y, plan = moe.routed_experts(m, idx, w, sl["gate"], sl["up"],
                                     sl["down"], first=first,
                                     num_experts=experts)
        parts_prog += y
    np.testing.assert_allclose(parts_ref, whole, atol=1e-5)
    np.testing.assert_allclose(parts_prog, whole, atol=1e-5)


def test_bias_selects_and_weights_are_scaled_normalised_scores(ref):
    p = ref.init_weights(ARCH, 3)["params"]["block_1"]
    m = jax.random.normal(jax.random.key(2), (64, 48))
    router = dict(p["moe"]["router"],
                  bias=jnp.zeros((8,)).at[5].set(10.0))
    idx, w = ref.route(ARCH, dict(p, moe=dict(p["moe"], router=router)), m,
                       None)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))     # it selects
    s = jax.nn.sigmoid(m @ router["kernel"])
    picked = jnp.take_along_axis(s, idx, axis=-1)        # and does not weigh
    np.testing.assert_allclose(
        w, 2.448 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    from tpuframe.ops import moe

    idx_p, w_p = moe.route_sigmoid_topk(m @ router["kernel"], router["bias"],
                                        k=2, scale=2.448, normalize=True)
    assert bool(jnp.array_equal(jnp.sort(idx_p, -1), jnp.sort(idx, -1)))
    np.testing.assert_allclose(jnp.sort(w_p, -1), jnp.sort(w, -1), rtol=1e-5)


# -- the latent flash kernels ------------------------------------------------


def _composition(q, qr, k, kr, v):
    """The scores as one product over the concatenated widths, the rotary
    key repeated for every head."""
    s = q.shape[1]
    kr = jnp.repeat(kr, q.shape[2] // kr.shape[2], axis=2)
    qq, kk = jnp.concatenate([q, qr], -1), jnp.concatenate([k, kr], -1)
    sc = jnp.einsum("bqnd,bknd->bnqk", qq, kk) * qq.shape[-1] ** -0.5
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    pr = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", pr, v)


def _operands(s, n, n_r, d=16, d_r=8, d_v=16, b=2):
    ks = jax.random.split(jax.random.key(0), 6)
    shapes = [(b, s, n, d), (b, s, n, d_r), (b, s, n, d), (b, s, n_r, d_r),
              (b, s, n, d_v), (b, s, n, d_v)]
    return [jax.random.normal(k, sh) for k, sh in zip(ks, shapes)]


@pytest.mark.parametrize("s,n,n_r,bq,bk", [
    (64, 4, 1, None, None),      # the toy block's attention: one block
    (512, 4, 1, 128, 128),       # several blocks, the key shared by 4 heads
    (512, 2, 1, 128, 256),       # K/V blocks wider than Q blocks
    (256, 8, 1, 256, 128),       # 8 heads, Q blocks wider than K/V blocks
    (512, 4, 4, 128, 128),       # the rotary key repeated: a head each
    (512, 4, 2, 256, None),      # two heads to a rotary key, K/V whole
])
def test_latent_flash_against_the_composition(s, n, n_r, bq, bk):
    from tpuframe.ops import flash_attention as fa

    *args, co = _operands(s, n, n_r)
    kern = lambda *a: fa.flash_mla(  # noqa: E731
        *a, block_q=bq, block_k=bk, interpret=True,
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(kern(*args), _composition(*args), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kern(*a) * co), range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(_composition(*a) * co),
                    range(5))(*args)
    for name, a, b in zip(("dq", "dq_rope", "dk", "dk_rope", "dv"), got,
                          want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_shared_and_repeated_rotary_key_give_the_same_numbers():
    """One row a position read by every head, or the same row stored once a
    head: the same output, the same dq, dk, dv; and the shared row's
    gradient is the sum of the repeated rows' over the heads."""
    from tpuframe.ops import flash_attention as fa

    q, qr, k, kr, v, co = _operands(256, 4, 1)
    kern = lambda *a: fa.flash_mla(  # noqa: E731
        *a, block_q=128, block_k=128, interpret=True,
        precision=jax.lax.Precision.HIGHEST)
    rep = jnp.repeat(kr, 4, axis=2)
    assert bool(jnp.array_equal(kern(q, qr, k, kr, v),
                                kern(q, qr, k, rep, v)))
    loss = lambda *a: jnp.sum(kern(*a) * co)  # noqa: E731
    one = jax.grad(loss, range(5))(q, qr, k, kr, v)
    many = jax.grad(loss, range(5))(q, qr, k, rep, v)
    for i in (0, 1, 2, 4):
        assert bool(jnp.array_equal(one[i], many[i]))
    assert one[3].shape == (2, 256, 1, 8) and many[3].shape == (2, 256, 4, 8)
    np.testing.assert_allclose(one[3][:, :, 0], many[3].sum(axis=2),
                               atol=1e-5)


@pytest.mark.parametrize("why", ["tiles", "does_not_tile", "window"])
def test_attention_op_takes_a_second_score_term(why, capsys):
    """``multihead_attention(rope=...)``: the kernels where the shapes
    tile, else the XLA composition, which says so; v of another width than
    q and k either way."""
    from tpuframe.ops import kernel_impl
    from tpuframe.ops.attention import multihead_attention

    kernel_impl.reset()
    s = 200 if why == "does_not_tile" else 64
    q, qr, k, kr, v, _ = _operands(s, 4, 1, d_v=32)
    kw = dict(window=16) if why == "window" else {}
    out = multihead_attention(q, k, v, rope=(qr, kr), causal=True,
                              impl="pallas", **kw)
    assert out.shape == (2, s, 4, 32)
    said = capsys.readouterr().out
    if why == "tiles":
        np.testing.assert_allclose(out, _composition(q, qr, k, kr, v),
                                   atol=2e-5)
        assert "flash_mla_attention -> interpret" in said
        assert "score products 16 + 8 deep, value products 32 wide" in said
    else:
        assert "flash_mla_attention -> xla" in said
    np.testing.assert_allclose(
        multihead_attention(q, k, v, rope=(qr, kr), causal=True, impl="xla"),
        _composition(q, qr, k, kr, v), atol=2e-5)


def test_tiling_rule_counts_the_rope_operands():
    """At the cell's shape the rule gives the walked operand less than it
    would without the rope pair, and says what fits the budget."""
    from tpuframe.ops import flash_attention as fa

    for kernel in ("fwd", "dq", "dkv"):
        plain = fa.choose_tiles(kernel, 8192, 8192, 128)
        rope = fa.choose_tiles(kernel, 8192, 8192, 128, rope_dim=64)
        assert fa.vmem_bytes(kernel, rope, 128, 2, 64) <= fa.VMEM_BUDGET
        assert fa.vmem_bytes(kernel, plain, 128, 2, 64) \
            > fa.vmem_bytes(kernel, plain, 128, 2)
        assert rope.block_q * rope.block_k <= plain.block_q * plain.block_k
