"""The LFM2-MoE model (``models/lfm2.py``) and what it brings with it: the
gated short convolution as a token mixer (against the layer's equations written
as a loop over positions, every leaf's gradient, causality), grouped-query
attention with per-head norms through the flash kernels, the shared expert
layer without a shared expert (all four shares against the uncut layer, a token
with no expert here getting exactly zero), the head tied to a sliced embedding,
and the whole model against the benchmark's plain reference
(``benchmarks/reference/lfm2.py``, which imports nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import lfm2, moe
from torchft_tpu.models.kimi_linear import layer_plan

TINY = lfm2.Lfm2Config(
    vocab_size=128, d_model=32, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, d_expert=16,
    n_routed_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32,
    attn_impl="dense")
CD, AM, CM = ("conv", "dense"), ("attn", "moe"), ("conv", "moe")


# ---- the pattern of layers and the tree ---------------------------------------

@pytest.mark.parametrize("cfg,plan", [
    (lfm2.Lfm2Config(), [((CD,), 2), ((AM, CM, CM, CM), 4), ((AM, CM, CM), 2)]),
    (lfm2.Lfm2Config(n_layers=6), [((CD,), 2), ((AM,), 1), ((CM,), 3)]),
    (dataclasses.replace(TINY, n_layers=10), [((CD,), 2), ((AM, CM, CM, CM), 2)]),
    (dataclasses.replace(TINY, num_dense_layers=0, n_layers=3), [((CM,), 2), ((AM,), 1)]),
    (dataclasses.replace(TINY, layer_types=("full_attention", "conv"), n_layers=6, num_dense_layers=1),
     [((("attn", "dense"),), 1), ((CM, AM), 2), ((CM,), 1)]),
], ids=["published-24", "cut-6", "two-periods", "no-dense", "another-list"])
def test_the_layer_plan_comes_from_layer_types_and_num_dense_layers(cfg, plan):
    """Layers from 0 as published: which operator from ``layer_types``, the
    dense FFN on the first ``num_dense_layers``; the published depth of 24 is
    eight layer bodies, the cut's six are three."""
    kinds = lfm2.layer_kinds(cfg)
    assert len(kinds) == cfg.n_layers
    assert layer_plan(kinds) == plan


def test_the_published_list_is_eighteen_convolutions_to_six_attentions():
    kinds = lfm2.layer_kinds(lfm2.Lfm2Config())
    assert [k[0] for k in kinds].count("conv") == 18 and [k[0] for k in kinds].count("attn") == 6
    assert [i for i, k in enumerate(kinds) if k[0] == "attn"] == [2, 6, 10, 14, 18, 21]
    assert [k[1] for k in kinds] == ["dense"] * 2 + ["moe"] * 22


def _count(shapes):
    return {g: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes[g])) for g in shapes}


def test_the_tree_counts_the_published_parameters():
    """8,339,929,856 with all 32 experts held and the head tied (the
    published 8.3 B); the cut's 568,647,808."""
    whole = dataclasses.replace(lfm2.Lfm2Config(), held_experts=tuple(range(32)))
    shapes = jax.eval_shape(lambda k: lfm2.init_params(k, whole), jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "embedding_norm", "conv", "attn", "dense", "moe"}, "no head: it is tied"
    count = _count(shapes)
    assert count["embed"] == 65536 * 2048 and count["conv"] == 18 * 16_785_408
    assert count["attn"] == 6 * 10_487_936 and count["dense"] == 2 * 44_042_240
    assert count["moe"] == 22 * 352_389_120 and "shared_gate" not in shapes["moe"]
    assert sum(count.values()) == 8_339_929_856
    assert sum(count.values()) + 65536 * 2048 == 8_474_147_584, "untied it would be 8.47 B"
    cut = lfm2.Lfm2Config(n_layers=6, vocab_size=16384)
    shapes = jax.eval_shape(lambda k: lfm2.init_params(k, cut), jax.random.PRNGKey(0))
    assert sum(_count(shapes).values()) == 568_647_808
    assert shapes["conv"]["conv"].shape == (5, 2048, 3) and shapes["moe"]["w_gate"].shape == (4, 8, 2048, 1792)
    first = {g: {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(shapes[g])} for g in lfm2.GROUPS}
    assert first == {"conv": {5}, "attn": {1}, "dense": {2}, "moe": {4}}


# ---- the gated short convolution -----------------------------------------------

def _mixer_leaves(cfg, key=3):
    params = lfm2.init_params(jax.random.PRNGKey(key), cfg)
    return jax.tree_util.tree_map(lambda w: w[0], params["conv"])


def _mixer_by_positions(h, p, taps):
    """The layer's equations one position at a time: ``[B | C | u] = h W_in``;
    ``z = B * u``; ``c_t = sum_j w[:, j] z_{t - (taps - 1) + j}``, nothing
    before the row's start; ``y = (C * c) W_out``."""
    bcu = h @ p["w_in"]
    d = h.shape[-1]
    b_gate, c_gate, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    z = b_gate * u
    rows = []
    for t in range(h.shape[1]):
        c_t = jnp.zeros_like(z[:, 0])
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                c_t = c_t + p["conv"][:, j] * z[:, at]
        rows.append(c_gate[:, t] * c_t)
    return jnp.stack(rows, axis=1) @ p["w_out"]


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_the_mixer_is_the_layers_equations_with_every_leafs_gradient(taps):
    cfg = dataclasses.replace(TINY, conv_taps=taps)
    p = _mixer_leaves(cfg)
    assert p["conv"].shape == (32, taps) and p["w_in"].shape == (32, 96)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 32))
    got = lfm2.short_conv_mixer(h, p, cfg)
    want = _mixer_by_positions(h, p, taps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    weigh = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    g_got = jax.grad(lambda h, p: (lfm2.short_conv_mixer(h, p, cfg) * weigh).sum(), argnums=(0, 1))(h, p)
    g_want = jax.grad(lambda h, p: (_mixer_by_positions(h, p, taps) * weigh).sum(), argnums=(0, 1))(h, p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0], jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(g_got[1]["operator_norm"]).max()) == 0, "the norm before the mixer is the layer's"


def test_the_mixer_is_causal_and_sees_two_positions_back():
    """Position ``t`` is unmoved by tokens after it, moved by the two before
    it (three taps) and by none further back."""
    p = _mixer_leaves(TINY)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 32))
    base = lfm2.short_conv_mixer(h, p, TINY)
    moved = lfm2.short_conv_mixer(h.at[:, 9].add(1.0), p, TINY)
    changed = np.asarray(jnp.abs(moved - base).max(axis=-1)[0] > 0)
    assert changed.tolist() == [False] * 9 + [True] * 3 + [False] * 4
    # the gradient walks the taps the other way: position 9's input hears of outputs 9, 10, 11 alone
    for out in range(16):
        g = jax.grad(lambda h: lfm2.short_conv_mixer(h, p, TINY)[0, out].sum())(h)
        assert bool(jnp.abs(g[0, 9]).max() > 0) == (out in (9, 10, 11)), out


def test_the_mixer_in_bfloat16_keeps_no_four_dimensional_intermediate():
    """The taps are shifted multiply-adds: nothing of shape ``[B, T, D, 3]``
    in the traced program, forward or backward; the float32 chain is
    recomputed from the first product's bfloat16 output."""
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    p = _mixer_leaves(cfg)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda h, p: lfm2.short_conv_mixer(h, p, cfg).astype(jnp.float32).sum(),
                                    argnums=(0, 1)))(h, p)

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    assert not [s for s in shapes(jaxpr.jaxpr) if len(s) == 4]
    got = lfm2.short_conv_mixer(h, p, cfg)
    want = _mixer_by_positions(h.astype(jnp.float32), p, 3)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=0.05, atol=0.05)


# ---- attention: grouped heads, q / k norms, the rotary ------------------------

def _attention_leaves(cfg, key=3):
    params = lfm2.init_params(jax.random.PRNGKey(key), cfg)
    p = jax.tree_util.tree_map(lambda w: w[0], params["attn"])
    # norms that are not ones, so that a norm left out shows
    return dict(p, q_layernorm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(8), p["q_layernorm"].shape),
                k_layernorm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(9), p["k_layernorm"].shape))


def _plain_attention(h, p, cfg):
    """A head at a time: key-value head ``i // g`` serves query head ``i``;
    per-head RMS norms, then the rotary on halves, the softmax at
    ``head_dim ** -0.5``."""
    b, t, _ = h.shape
    nh, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps) * w

    def rope(x):
        inv = cfg.rope_theta ** (-jnp.arange(0, dh, 2) / dh)
        angle = jnp.arange(t)[:, None] * inv[None]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    q = (h @ p["wq"]).reshape(b, t, nh, dh)
    k = (h @ p["wk"]).reshape(b, t, nkv, dh)
    v = (h @ p["wv"]).reshape(b, t, nkv, dh)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None]
    heads = []
    for i in range(nh):
        q_i = rope(rms(q[:, :, i], p["q_layernorm"]))
        k_i = rope(rms(k[:, :, i // (nh // nkv)], p["k_layernorm"]))
        s = jnp.where(seen, q_i @ jnp.swapaxes(k_i, 1, 2) / np.sqrt(dh), -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ v[:, :, i // (nh // nkv)])
    return jnp.stack(heads, 2).reshape(b, t, nh * dh) @ p["wo"]


def test_attention_is_the_layers_equations():
    p = _attention_leaves(TINY)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 32))
    np.testing.assert_allclose(np.asarray(lfm2._attention(h, p, TINY)), np.asarray(_plain_attention(h, p, TINY)),
                               rtol=2e-4, atol=2e-5)
    # the rotary carries the position: at another theta the output differs, but not at position 0
    other = lfm2._attention(h, p, dataclasses.replace(TINY, rope_theta=100.0))
    assert float(jnp.abs(other - lfm2._attention(h, p, TINY))[:, 1:].max()) > 1e-4
    np.testing.assert_allclose(np.asarray(other[:, 0]), np.asarray(lfm2._attention(h, p, TINY)[:, 0]), rtol=1e-5, atol=1e-6)


def test_attention_through_the_flash_kernels_is_dense_attention():
    """32 / 8 heads of 64 at a small hidden size: grouped four to one, the
    q / k norms and the rotary before the kernels, the kernels interpreted,
    gradients of every leaf."""
    cfg = dataclasses.replace(TINY, d_model=64, n_heads=8, n_kv_heads=2, head_dim=64, rope_theta=1e6)
    p = _attention_leaves(cfg)
    assert p["wq"].shape == (64, 512) and p["wk"].shape == (64, 128)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))

    def out(impl, h, p):
        return lfm2._attention(h, p, dataclasses.replace(cfg, attn_impl=impl))

    np.testing.assert_allclose(np.asarray(out("flash", h, p)), np.asarray(out("dense", h, p)), rtol=2e-4, atol=2e-5)
    g_flash = jax.grad(lambda h, p: (out("flash", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    g_dense = jax.grad(lambda h, p: (out("dense", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_flash)[0], jax.tree_util.tree_leaves(g_dense)):
        if not b.size or float(jnp.abs(b).max()) == 0:
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))
    with pytest.raises(ValueError, match="attn_impl"):
        out("ring", h, p)


# ---- the router: its epsilon, its bias ----------------------------------------

def test_the_routers_epsilon_is_this_models_and_its_bias_a_buffer():
    cfg = TINY.moe()
    assert (cfg.shared, cfg.renorm_eps, cfg.routed_scale, cfg.top_k, cfg.n_routed) == (False, 1e-6, 1.0, 4, 16)
    assert lfm2.Lfm2Config().moe().held == tuple(range(8)) and lfm2.Lfm2Config().moe().n_routed == 32
    # logits near -14, scores near 1e-6: the epsilon shows
    flat = jnp.full((50, 32), 14.0 / 32) + 0.01 * jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    router = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (32, 16)) - 1.0
    chosen, weights = moe.route_sigmoid(flat, router, cfg)
    scores = jax.nn.sigmoid(flat @ router)
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6)), rtol=1e-5)
    assert 0.5 < float(weights.sum(-1).max()) < 0.9, "with sums near 1e-6 the weights no longer add up to one"
    # the bias moves the choice, not the weights' source, and takes no gradient
    bias = jnp.zeros((16,)).at[5].set(10.0)
    pushed, w_pushed = moe.route_sigmoid(flat, router, cfg, bias)
    assert bool((pushed == 5).any(-1).all())
    np.testing.assert_allclose(np.asarray(jnp.take_along_axis(scores, pushed, -1) / (
        jnp.take_along_axis(scores, pushed, -1).sum(-1, keepdims=True) + 1e-6)), np.asarray(w_pushed), rtol=1e-5)


def test_the_expert_bias_is_a_buffer_of_the_model():
    params = lfm2.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, TINY.vocab_size)
    zeros = jnp.zeros((4, 16))
    base = lfm2.make_routing_stats(TINY)(params, tokens)
    same = lfm2.make_routing_stats(TINY, zeros)(params, tokens)
    np.testing.assert_array_equal(np.asarray(base["assignments"]), np.asarray(same["assignments"]))
    pushed = lfm2.make_routing_stats(TINY, zeros.at[1, 0].set(10.0))(params, tokens)
    np.testing.assert_array_equal(np.asarray(pushed["assignments"][0]), np.asarray(base["assignments"][0]))
    assert int(pushed["assignments"][1, 0]) == tokens.size, "every token of the second expert layer now picks expert 0"
    assert int(pushed["unrouted"][1]) == 0
    grad = jax.jit(jax.grad(lambda bias: lfm2.loss_fn(params, tokens, TINY, bias)))(zeros)
    assert float(jnp.abs(grad).max()) == 0.0


# ---- the share of the expert layer: nothing is shared ---------------------------

def _uncut_layer(x, p, top_k, eps):
    """The whole layer, every expert on every token with the weights as a
    mask; no shared expert."""
    flat = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(flat @ p["router"])
    _, chosen = jax.lax.top_k(scores, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + eps)
    out = jnp.zeros_like(flat)
    for e in range(p["w_gate"].shape[0]):
        glu = (jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e])) @ p["w_down"][e]
        out = out + jnp.where(chosen == e, weight, 0.0).sum(-1, keepdims=True) * glu
    return out.reshape(x.shape)


def test_the_four_shares_add_up_to_the_uncut_layer_and_nothing_is_counted_once():
    """The deployment's cut at a small size: 32 experts scored, 4 a token, 8
    held by each of 4 chips (ids 0-7, 8-15, 16-23, 24-31).  The four shares'
    outputs, simply added (no shared expert to count once), are the uncut
    layer; every assignment lands on one share; a token none of whose experts
    lives on a share gets exactly zero from it."""
    d, f, n_routed, top_k = 32, 12, 32, 4
    model = dataclasses.replace(TINY, d_model=d, d_expert=f, n_routed_experts=n_routed, experts_per_token=top_k)
    whole = dataclasses.replace(model, held_experts=tuple(range(n_routed))).moe()
    full = jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(8), whole, 1))
    assert sorted(full) == ["router", "w_down", "w_gate", "w_up"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, d))
    _, chosen = jax.lax.top_k(jax.nn.sigmoid(x.reshape(-1, d) @ full["router"]), top_k)
    total, landed, unrouted = 0.0, 0, []
    for share in range(4):
        held = tuple(range(8 * share, 8 * share + 8))
        cfg = dataclasses.replace(model, held_experts=held).moe()
        assert (cfg.n_routed, cfg.top_k, cfg.held, cfg.shared) == (32, 4, held, False)
        mine = dict(full, **{name: full[name][np.asarray(held)] for name in ("w_gate", "w_up", "w_down")})
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        nowhere = np.asarray(((chosen < held[0]) | (chosen > held[-1])).all(-1))
        assert int(stats["unrouted"]) == int(nowhere.sum()) > 0
        assert np.all(np.asarray(y).reshape(-1, d)[nowhere] == 0.0), "no expert here, nothing from the FFN"
        assert np.all(np.abs(np.asarray(y).reshape(-1, d)[~nowhere]).max(-1) > 0)
        total = total + y
        landed += int(stats["assignments"].sum())
        unrouted.append(int(stats["unrouted"]))
    assert landed == 2 * 40 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(_uncut_layer(x, full, top_k, 1e-6)), rtol=2e-4, atol=2e-5)
    # under uniform routing C(24, 4) / C(32, 4) = 29.5 % of the tokens find none of theirs on a share
    assert 0.1 < np.mean(unrouted) / 80 < 0.5


def test_the_masked_path_gives_an_unrouted_token_zero_too():
    """A pool too small for what landed: every held expert over every token
    with the weights as a mask; the same output, zero where nothing landed."""
    d = 32
    cfg = dataclasses.replace(TINY, d_model=d).moe()
    p = jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(8), cfg, 1))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, d))
    gathered, stats = moe.held_moe_ffn(x, p, cfg)
    masked, _ = moe.held_moe_ffn(x, p, dataclasses.replace(cfg, slack=0.01))
    assert int(stats["assignments"].sum()) > 8, "more landed than the small pool holds"
    np.testing.assert_allclose(np.asarray(masked), np.asarray(gathered), rtol=2e-4, atol=2e-5)
    _, chosen = jax.lax.top_k(jax.nn.sigmoid(x.reshape(-1, d) @ p["router"]), 4)
    nowhere = np.asarray((chosen >= 4).all(-1))
    assert nowhere.any() and np.all(np.asarray(masked).reshape(-1, d)[nowhere] == 0.0)


# ---- the whole model against the plain reference ------------------------------

def _reference_sizes(cfg):
    return {
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "num_dense_layers": cfg.num_dense_layers,
        "layer_types": [cfg.layer_types[i % len(cfg.layer_types)] for i in range(cfg.n_layers)],
        "num_experts_per_tok": cfg.experts_per_token, "held_expert_ids": list(cfg.held_experts),
        "routed_scaling_factor": cfg.routed_scaling_factor}


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=10, held_experts=(3, 8, 9, 15)),
    dataclasses.replace(TINY, n_layers=3, num_dense_layers=0, remat=False, routed_scaling_factor=2.0),
    dataclasses.replace(TINY, n_layers=4, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, conv_taps=4,
                        remat_policy="dots"),
    dataclasses.replace(TINY, n_layers=3, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64, attn_impl="flash"),
], ids=["the-cuts-six-layers", "two-scanned-periods", "no-dense-no-remat", "four-taps-one-kv-head",
        "through-the-flash-kernels"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Loss and every gradient leaf on seeded weights, the tied embedding's
    (two uses) among them; the reference shifts the row three times, forms
    ``[T, T]`` scores a head at a time and runs the experts one at a time."""
    from benchmarks.reference.lfm2 import loss_fn as reference_loss

    assert cfg.head_dim == cfg.d_model // cfg.n_heads, "the reference takes the head's width as hidden / heads"
    t = 128 if cfg.attn_impl == "flash" else 96
    params = lfm2.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, t), 0, cfg.vocab_size)
    loss, grads = lfm2.make_grad_step(cfg)(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, _reference_sizes(cfg), None)))(params, tokens)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == 22
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape
        if not r.size:  # a group this pattern has no layer of
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_reads_the_lower_precision_control():
    """With float8 operands the reference's loss moves, with bfloat16 less:
    the knob reaches every product."""
    from benchmarks.reference.lfm2 import loss_fn as reference_loss

    params = lfm2.init_params(jax.random.PRNGKey(5), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, TINY.vocab_size)
    sizes = _reference_sizes(TINY)
    exact, half, eighth = (float(jax.jit(lambda p, d=d: reference_loss(p, tokens, sizes, d))(params))
                           for d in (None, "bfloat16", "float8_e4m3fn"))
    assert 0 < abs(half - exact) < abs(eighth - exact) < 0.2 * exact


def test_logits_and_loss_agree():
    params = lfm2.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, TINY.vocab_size)
    logits = jax.jit(lambda p: lfm2.forward(p, tokens, TINY))(params)
    assert logits.shape == (2, 64, TINY.vocab_size) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(float(jax.jit(lambda p: lfm2.loss_fn(p, tokens, TINY))(params)), float(want), rtol=1e-5)


def test_bfloat16_compute_keeps_float32_parameters_and_gradients():
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    params = lfm2.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, cfg.vocab_size)
    loss, grads = lfm2.make_grad_step(cfg)(params, tokens)
    want = jax.jit(lambda p: lfm2.loss_fn(p, tokens, TINY))(params)
    assert loss.dtype == jnp.float32 and abs(float(loss) - float(want)) < 0.02 * float(want)
    assert all(g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


# ---- the tied head on a sliced vocabulary --------------------------------------

def test_the_tied_embedding_gathers_both_uses_gradients_on_a_sliced_vocabulary():
    """A quarter of the rows (the chip's slice): ids drawn from it, logits
    over it; the embedding's gradient is the sum of the lookup's and the
    head's, and a row no token names still learns from the head."""
    cfg = dataclasses.replace(TINY, vocab_size=32)   # a quarter of TINY's 128
    params = lfm2.init_params(jax.random.PRNGKey(7), cfg)
    assert params["embed"].shape == (32, 32) and "head" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 48), 0, 24)   # rows 24-31 are never looked up
    _, whole = lfm2.make_grad_step(cfg)(params, tokens)

    def _two_uses(lookup, head):
        x, _ = lfm2.forward_hidden(dict(params, embed=lookup), tokens, cfg)
        logits = lfm2._logits(dict(params, embed=head), x, cfg)
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    by_lookup, by_head = jax.jit(jax.grad(_two_uses, argnums=(0, 1)))(params["embed"], params["embed"])
    np.testing.assert_allclose(np.asarray(whole["embed"]), np.asarray(by_lookup + by_head), rtol=1e-4,
                               atol=1e-6 * float(jnp.abs(by_head).max()))
    assert float(jnp.abs(by_lookup[24:]).max()) == 0 and float(jnp.abs(by_head[24:]).max()) > 0
    assert float(jnp.abs(by_lookup[:24]).max()) > 0
    logits = lfm2.forward(params, tokens, cfg)
    assert logits.shape == (2, 48, 32), "the logits are over the slice"


# ---- routing stats and the counters --------------------------------------------

def _read(name, **labels):
    from torchft_tpu.utils import metrics

    samples = metrics.parse_text_exposition(metrics.REGISTRY.render()).get(name, {"samples": {}})["samples"]
    return {(n, tuple(sorted(l))): v for (n, l), v in samples.items()}.get(
        (name, tuple(sorted(labels.items()))), 0.0)


def test_routing_stats_over_all_shares_count_every_assignment():
    params = lfm2.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, TINY.vocab_size)
    landed = 0
    for share in range(4):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(4 * share, 4 * share + 4)))
        stats = lfm2.make_routing_stats(cfg)(params, tokens)
        assert stats["assignments"].shape == (4, 4) and stats["unrouted"].shape == (4,)
        # the layers before the first expert layer are the same on every share
        landed += int(stats["assignments"][0].sum())
    assert landed == tokens.size * TINY.experts_per_token


def test_routing_stats_feed_the_shared_counters_under_the_models_layer_numbers():
    """Through ``models/moe.py`` ``record_routing_stats``, as the other sparse
    families: the cut's expert layers are layers 2-5 of the published model,
    experts by their published id; the unrouted counter counts tokens whose
    FFN output is zero here."""
    cfg = dataclasses.replace(TINY, held_experts=(2, 5, 11, 12))
    params = lfm2.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
    stats = lfm2.make_routing_stats(cfg)(params, tokens)
    layers = (2, 3, 4, 5)
    keys = [(row, layer, slot, e) for row, layer in enumerate(layers) for slot, e in enumerate(cfg.held_experts)]
    before = [_read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) for _, layer, _, e in keys]
    lost = [_read("torchft_moe_tokens_unrouted_total", layer=str(layer)) for layer in layers]
    lfm2.record_routing_stats(stats, cfg)
    for (row, layer, slot, e), was in zip(keys, before):
        assert _read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) - was == int(
            stats["assignments"][row, slot])
    for row, (layer, was) in enumerate(zip(layers, lost)):
        assert _read("torchft_moe_tokens_unrouted_total", layer=str(layer)) - was == int(stats["unrouted"][row])
    assert int(stats["unrouted"].sum()) > 0


def test_the_step_keeps_the_flash_forwards_results_and_opens_the_models_scopes():
    """Full remat through ``transformer._remat``: the one attention layer's
    flash forward is kept (the gauge reads its bytes); the lowered program
    names the scopes the per-layer metrics read, and no ``moe.shared``."""
    cfg = dataclasses.replace(TINY, n_layers=3, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64,
                              attn_impl="flash", dtype=jnp.bfloat16)
    params = lfm2.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, cfg.vocab_size)
    step = lfm2.make_grad_step(cfg)
    text = step.lower(params, tokens).as_text(debug_info=True)
    kept = _read("torchft_remat_kept_bytes")
    assert kept == 2 * 128 * 2 * 64 * 2 + 2 * 2 * 128 * 4, "one layer's B T H Dv x 2 B + B H T x 4 B"
    for scope in ("shortconv", "shortconv.proj", "shortconv.mix", "attn", "attn.proj", "moe.route",
                  "moe.experts", "moe.gathered", "moe.masked", "ffn.dense", "head", "embed"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert "moe.shared" not in text


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    """The model's gradient tree at a small size: 22 leaves in four stacks, a
    convolution leaf whose last dimension is 3, four-dimensional expert
    leaves, no head."""
    params = lfm2.init_params(jax.random.PRNGKey(11), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0, TINY.vocab_size)
    _, grads = lfm2.make_grad_step(TINY)(params, tokens)
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    grads = _gradient_tree()
    leaves, tree = jax.tree_util.tree_flatten(grads)
    assert len(leaves) == 22 and max(leaf.ndim for leaf in leaves) == 4
    assert grads["conv"]["conv"].shape == (5, 32, 3), "the leaf a TPU hands to the host strided"
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/lfm2", f"rank{r}", r, 2), range(2)))
            sides = [leaves, [3.0 * np.asarray(leaf) for leaf in leaves]]
            out = list(ex.map(lambda r: pgs[r].allreduce(sides[r], REDUCE_AVG).wait(timeout=60), range(2)))
    finally:
        for pg in pgs:
            pg.shutdown()
        store.shutdown()
    for res in out:
        assert jax.tree_util.tree_structure(jax.tree_util.tree_unflatten(tree, res)) == tree
        for got, leaf in zip(res, leaves):
            assert got.shape == leaf.shape and got.dtype == leaf.dtype
            np.testing.assert_allclose(np.asarray(got), 2.0 * np.asarray(leaf), rtol=1e-6, atol=1e-12)


@pytest.mark.parametrize("fragments", [1, 6, 64])
def test_the_heal_fragments_carry_the_new_tree_bitwise(fragments):
    from torchft_tpu.checkpointing import fragments as frags

    state = {"params": _gradient_tree(), "step": 7}
    header, parts = frags.iter_heal_fragments(state, fragments)
    leaves = {}
    for _name, raw, _digest in parts:
        leaves.update(frags.decode_fragment(raw))
    back = frags.assemble(header, leaves)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(state)
    for got, want in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(state)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    conv = np.asarray(back["params"]["conv"]["conv"])
    assert conv.shape == (5, 32, 3) and conv.tobytes() == np.asarray(state["params"]["conv"]["conv"]).tobytes()
