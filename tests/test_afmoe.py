"""The AFMoE model (``models/afmoe.py``: Trinity) and what it brings with it:
window layers beside global ones through the flash kernels, gated heads, norms
on both sides of a sub-block, the shared expert layer at this router's widths
(all 16 shares against the uncut layer), and the whole model against the
benchmark's plain reference (``benchmarks/reference/afmoe.py``, which imports
nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import afmoe, moe
from torchft_tpu.models.kimi_linear import layer_plan

S, F = "sliding_attention", "full_attention"
TINY = afmoe.AfmoeConfig(
    vocab_size=128, d_model=32, n_layers=6, layer_types=(S, S, S, F), num_dense_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=8, sliding_window=24, d_ff=64, d_expert=16, n_routed_experts=16,
    experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32, attn_impl="dense")
LD, LM, GM = ("local", "dense"), ("local", "moe"), ("global", "moe")


# ---- the pattern of layers ---------------------------------------------------

@pytest.mark.parametrize("cfg,plan", [
    (afmoe.AfmoeConfig(), [((LD,), 2), ((LM, GM, LM, LM), 7), ((LM, GM), 1)]),
    (TINY, [((LD,), 2), ((LM, GM), 1), ((LM,), 2)]),
    (dataclasses.replace(TINY, n_layers=4, num_dense_layers=0, layer_types=(S, F)), [((LM, GM), 2)]),
], ids=["published-32", "cut-6", "every-other-global"])
def test_layer_kinds_follow_layer_types(cfg, plan):
    """Layers from 0 as published: the first two dense, a global layer every
    fourth; the published depth is seven layer bodies, not 32."""
    kinds = afmoe.layer_kinds(cfg)
    assert [kind[0] == "global" for kind in kinds] == [
        cfg.layer_types[i % len(cfg.layer_types)] == F for i in range(cfg.n_layers)]
    assert [kind[1] for kind in kinds] == ["dense"] * cfg.num_dense_layers + ["moe"] * (
        cfg.n_layers - cfg.num_dense_layers)
    assert layer_plan(kinds) == plan


def test_the_tree_is_four_stacks_by_kind_of_layer():
    params = afmoe.init_params(jax.random.PRNGKey(0), TINY)
    assert set(params) == {"embed", "head", "final_norm", "local", "global", "dense", "moe"}
    first = {g: {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(params[g])} for g in afmoe.GROUPS}
    assert first == {"local": {5}, "global": {1}, "dense": {2}, "moe": {4}}
    assert params["local"]["wg"].shape == (5, 32, 32) and params["local"]["q_norm"].shape == (5, 8)
    assert params["head"].shape == (32, 128) and params["embed"].shape == (128, 32)
    # the published count: 569,167,360 at the cell's sizes, by the shapes alone
    cell = afmoe.AfmoeConfig(vocab_size=25024, n_layers=6)
    shapes = jax.eval_shape(lambda k: afmoe.init_params(k, cell), jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) == 569_167_360


# ---- attention: window, rotary, gate, norms ----------------------------------

def _one_layer(kind, **over):
    cfg = dataclasses.replace(TINY, n_layers=1, num_dense_layers=1, layer_types=(kind,), **over)
    params = afmoe.init_params(jax.random.PRNGKey(1), cfg)
    group = "local" if kind == S else "global"
    return cfg, jax.tree_util.tree_map(lambda w: w[0], params[group])


def _plain_attention(h, p, cfg, local):
    """The layer's equations, one head at a time."""
    b, t, _ = h.shape
    nh, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * w

    def rope(x):
        freqs = cfg.rope_theta ** (-jnp.arange(0, dh, 2) / dh)
        angle = jnp.arange(t)[:, None] * freqs[None]
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    q = rms((h @ p["wq"]).reshape(b, t, nh, dh), p["q_norm"])
    k = rms((h @ p["wk"]).reshape(b, t, nkv, dh), p["k_norm"])
    v = (h @ p["wv"]).reshape(b, t, nkv, dh)
    if local:
        q, k = rope(q), rope(k)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None]
    seen = (ahead >= 0) & ((ahead < cfg.sliding_window) | (not local))
    heads = []
    for head in range(nh):
        kv = head // (nh // nkv)
        s = jnp.where(seen, q[:, :, head] @ jnp.swapaxes(k[:, :, kv], 1, 2) / np.sqrt(dh), -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ v[:, :, kv])
    o = jnp.stack(heads, 2).reshape(b, t, nh * dh)
    return (o * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]


@pytest.mark.parametrize("kind", [S, F])
def test_attention_is_the_layers_equations(kind):
    """Norms of q and k per head, rotary on a window layer only, the band's
    mask, grouped queries two to one, the gate before the output projection."""
    cfg, p = _one_layer(kind)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 32))
    got = afmoe._attention(h, p, cfg, kind == S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_plain_attention(h, p, cfg, kind == S)),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", [S, F])
def test_attention_through_the_flash_kernels_is_dense_attention(kind):
    """Heads of 128 grouped two to one, the kernels interpreted; a window
    shorter than the sequence walks a band of two 128-tiles."""
    cfg, p = _one_layer(kind, d_model=64, n_heads=2, n_kv_heads=1, head_dim=128, sliding_window=160)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 384, 64))

    def out(impl, h, p):
        return afmoe._attention(h, p, dataclasses.replace(cfg, attn_impl=impl), kind == S)

    np.testing.assert_allclose(np.asarray(out("flash", h, p)), np.asarray(out("dense", h, p)),
                               rtol=2e-4, atol=2e-5)
    g_flash = jax.grad(lambda h, p: (out("flash", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    g_dense = jax.grad(lambda h, p: (out("dense", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    for a, b in zip(jax.tree_util.tree_leaves(g_flash), jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(b).max()))


def test_a_global_layer_carries_no_position_and_a_window_layer_forgets():
    """A key 24 or more positions back moves a window layer's output not at
    all and a global layer's; rotating is the window layer's alone: with the
    window as long as the sequence the two differ, but not at position 0."""
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 32))
    moved = h.at[:, 0].add(1.0)
    cfg_s, p = _one_layer(S)
    cfg_f, _ = _one_layer(F)
    local = lambda x: afmoe._attention(x, p, cfg_s, True)      # noqa: E731
    glob = lambda x: afmoe._attention(x, p, cfg_f, False)      # noqa: E731
    np.testing.assert_array_equal(np.asarray(local(h)[:, 24:]), np.asarray(local(moved)[:, 24:]))
    assert float(jnp.abs(glob(h)[:, 24:] - glob(moved)[:, 24:]).max()) > 1e-4
    wide = dataclasses.replace(cfg_s, sliding_window=64)
    assert float(jnp.abs(afmoe._attention(h, p, wide, True) - glob(h)).max()) > 1e-3
    # position 0 is turned by no angle: there the two are one
    np.testing.assert_allclose(np.asarray(afmoe._attention(h, p, wide, True)[:, 0]), np.asarray(glob(h)[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_a_sub_blocks_output_is_normed_before_it_joins_the_residual():
    """Four norms a layer: scaling ``wo`` (or ``w_down``) changes nothing, the
    norm after the sub-block takes it out; scaling the post norm's weight does."""
    cfg = dataclasses.replace(TINY, n_layers=1, num_dense_layers=1, rms_norm_eps=1e-12)
    params = afmoe.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 32), 0, cfg.vocab_size)
    base = afmoe.forward(params, tokens, cfg)

    def scaled(group, name, by):
        changed = dict(params, **{group: dict(params[group], **{name: params[group][name] * by})})
        return afmoe.forward(changed, tokens, cfg)

    np.testing.assert_allclose(np.asarray(scaled("local", "wo", 3.0)), np.asarray(base), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(scaled("dense", "w_down", 3.0)), np.asarray(base), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(scaled("local", "post_attn_norm", 3.0) - base).max()) > 1e-3
    assert float(jnp.abs(scaled("dense", "post_mlp_norm", 3.0) - base).max()) > 1e-3


def test_the_embedding_is_scaled_by_the_root_of_the_width():
    cfg = dataclasses.replace(TINY, n_layers=0, num_dense_layers=0)
    params = afmoe.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jnp.arange(16)[None]
    x, _ = afmoe.forward_hidden(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(x[0]), np.asarray(params["embed"][:16]) * np.sqrt(32), rtol=1e-6)
    plain, _ = afmoe.forward_hidden(params, tokens, dataclasses.replace(cfg, mup_enabled=False))
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(params["embed"][:16]))


# ---- the share of the expert layer at this router's widths --------------------

def _uncut_layer(x, p, top_k, scale):
    """The whole layer, every expert on every token with the weights as a mask."""
    flat = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(flat @ p["router"])
    _, chosen = jax.lax.top_k(scores, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale

    def glu(g, u, d):
        return (jax.nn.silu(flat @ g) * (flat @ u)) @ d

    out = glu(p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in range(p["w_gate"].shape[0]):
        out = out + jnp.where(chosen == e, weight, 0.0).sum(-1, keepdims=True) * glu(
            p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return out.reshape(x.shape)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The deployment's cut at a small size: 128 experts scored, 8 a token, 8
    held by each of 16 chips.  What all 16 shares give, the shared expert
    counted once, is the uncut layer; every assignment lands on one share."""
    d, f, n_routed, top_k, shares = 32, 16, 128, 8, 16
    whole = moe.HeldMoEConfig(d_model=d, d_expert=f, n_routed=n_routed, top_k=top_k,
                              held=tuple(range(n_routed)), dtype=jnp.float32)
    full = jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(8), whole, 1))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, d))
    flat = x.reshape(-1, d)
    shared = ((jax.nn.silu(flat @ full["shared_gate"]) * (flat @ full["shared_up"]))
              @ full["shared_down"]).reshape(x.shape)
    total, landed = 0.0, 0
    for share in range(shares):
        held = tuple(range(8 * share, 8 * share + 8))
        cfg = dataclasses.replace(TINY, d_model=d, d_expert=f, n_routed_experts=n_routed,
                                  experts_per_token=top_k, held_experts=held).moe()
        assert (cfg.n_routed, cfg.top_k, cfg.held, cfg.routed_scale) == (128, 8, held, 2.826)
        mine = dict(full, **{name: full[name][np.asarray(held)] for name in ("w_gate", "w_up", "w_down")})
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        total = total + (y - shared)
        landed += int(stats["assignments"].sum())
    assert landed == 2 * 40 * top_k
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(_uncut_layer(x, full, top_k, 2.826)),
                               rtol=2e-4, atol=2e-5)


# ---- the whole model against the plain reference ------------------------------

def _reference_sizes(cfg):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "sliding_window": cfg.sliding_window, "hidden_size": cfg.d_model, "mup_enabled": cfg.mup_enabled,
        "layer_types": [cfg.layer_types[i % len(cfg.layer_types)] for i in range(cfg.n_layers)],
        "num_dense_layers": cfg.num_dense_layers, "num_experts_per_tok": cfg.experts_per_token,
        "held_expert_ids": list(cfg.held_experts), "route_scale": cfg.route_scale}


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=10, held_experts=(3, 8, 9, 15)),
    dataclasses.replace(TINY, n_layers=3, num_dense_layers=0, layer_types=(F, S), sliding_window=200,
                        remat=False, mup_enabled=False),
    dataclasses.replace(TINY, n_layers=4, d_model=64, n_heads=2, n_kv_heads=1, head_dim=128,
                        sliding_window=130, attn_impl="flash"),
], ids=["cut-6", "a-period-scanned", "no-dense-no-remat-window-past-the-end", "through-the-flash-kernels"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Loss and every gradient leaf, on seeded weights, with both kinds of
    attention layer and both kinds of FFN; 96 tokens a row are four windows
    (three quarters of a 128-tile for the kernels' case, at 128 a row)."""
    from benchmarks.reference.afmoe import loss_fn as reference_loss

    t = 128 if cfg.attn_impl == "flash" else 96
    params = afmoe.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, t), 0, cfg.vocab_size)
    loss, grads = afmoe.make_grad_step(cfg)(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, _reference_sizes(cfg), None)))(params, tokens)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == 35
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape
        if not r.size:  # a group this pattern has no layer of
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


def test_logits_and_loss_agree():
    params = afmoe.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, TINY.vocab_size)
    logits = afmoe.forward(params, tokens, TINY)
    assert logits.shape == (2, 64, TINY.vocab_size) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(float(afmoe.loss_fn(params, tokens, TINY)), float(want), rtol=1e-5)


def test_bfloat16_compute_keeps_float32_parameters_and_gradients():
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    params = afmoe.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, cfg.vocab_size)
    loss, grads = afmoe.make_grad_step(cfg)(params, tokens)
    want = afmoe.loss_fn(params, tokens, TINY)
    assert loss.dtype == jnp.float32 and abs(float(loss) - float(want)) < 0.02 * float(want)
    assert all(g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_an_unknown_attention_is_refused():
    params = afmoe.init_params(jax.random.PRNGKey(2), TINY)
    with pytest.raises(ValueError, match="attn_impl"):
        afmoe.loss_fn(params, jnp.zeros((1, 32), jnp.int32), dataclasses.replace(TINY, attn_impl="ring"))


# ---- routing stats and their counters ----------------------------------------

def test_routing_stats_over_all_shares_count_every_assignment():
    params = afmoe.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, TINY.vocab_size)
    landed = 0
    for share in range(4):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(4 * share, 4 * share + 4)))
        stats = afmoe.make_routing_stats(cfg)(params, tokens)
        assert stats["assignments"].shape == (4, 4) and stats["unrouted"].shape == (4,)
        # the layers before the first expert layer are the same on every share
        landed += int(stats["assignments"][0].sum())
    assert landed == tokens.size * TINY.experts_per_token


def test_routing_stats_feed_the_shared_counters():
    """Through ``models/moe.py`` ``record_routing_stats``, as the other sparse
    family: layers by their number from 0, experts by their published id."""
    from torchft_tpu.utils import metrics

    cfg = dataclasses.replace(TINY, held_experts=(2, 5, 11, 12))
    params = afmoe.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
    stats = afmoe.make_routing_stats(cfg)(params, tokens)

    def read(name, **labels):
        samples = metrics.parse_text_exposition(metrics.REGISTRY.render()).get(name, {"samples": {}})["samples"]
        return {(n, tuple(sorted(l))): v for (n, l), v in samples.items()}.get(
            (name, tuple(sorted(labels.items()))), 0.0)

    keys = [(row, layer, slot, expert) for row, layer in enumerate((2, 3, 4, 5))
            for slot, expert in enumerate(cfg.held_experts)]
    before = [read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) for _, layer, _, e in keys]
    lost = [read("torchft_moe_tokens_unrouted_total", layer=str(layer)) for layer in (2, 3, 4, 5)]
    afmoe.record_routing_stats(stats, cfg)
    for (row, layer, slot, e), was in zip(keys, before):
        assert read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) - was == int(
            stats["assignments"][row, slot])
    for row, (layer, was) in enumerate(zip((2, 3, 4, 5), lost)):
        assert read("torchft_moe_tokens_unrouted_total", layer=str(layer)) - was == int(stats["unrouted"][row])


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    """The model's gradient tree at a small size: 35 leaves in four stacked
    groups, a norm of 8 a head, a four-dimensional expert leaf."""
    params = afmoe.init_params(jax.random.PRNGKey(11), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0, TINY.vocab_size)
    _, grads = afmoe.make_grad_step(TINY)(params, tokens)
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    leaves, tree = jax.tree_util.tree_flatten(_gradient_tree())
    assert len(leaves) == 35 and max(leaf.ndim for leaf in leaves) == 4
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/afmoe", f"rank{r}", r, 2), range(2)))
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
