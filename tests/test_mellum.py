"""The Mellum model (``models/mellum.py``) and what it brings with it: two
rotary tables in one model, chosen by the kind of layer (against the rule's
equations written as a loop over pairs), window and global attention through
the flash kernels with the scaled table, the shared expert layer routed by a
softmax and holding more experts than a token chooses (all four shares
against the uncut layer, a token with no expert here getting exactly zero),
and the whole model against the benchmark's plain reference
(``benchmarks/reference/mellum.py``, which imports nothing of the program)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import mellum, moe
from torchft_tpu.models.kimi_linear import layer_plan
from torchft_tpu.models.transformer import _rope, _rotate

TINY = mellum.MellumConfig(
    vocab_size=128, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=8, sliding_window=16,
    d_expert=16, n_routed_experts=16, experts_per_token=4, held_experts=tuple(range(8)),
    rope_global=mellum.RopeRule("yarn", factor=16.0, original_length=64, attention_factor=1.2772588722239782),
    dtype=jnp.float32, attn_impl="dense")
LM, GM = ("local", "moe"), ("global", "moe")


# ---- the pattern of layers and the tree ---------------------------------------

@pytest.mark.parametrize("cfg,plan", [
    (mellum.MellumConfig(), [((LM, LM, LM, GM), 7)]),
    (mellum.MellumConfig(n_layers=4), [((LM,), 3), ((GM,), 1)]),
    (dataclasses.replace(TINY, n_layers=8), [((LM, LM, LM, GM), 2)]),
    (dataclasses.replace(TINY, layer_types=("full_attention", "sliding_attention"), n_layers=5),
     [((GM, LM), 2), ((GM,), 1)]),
], ids=["published-28", "cut-4", "two-periods", "another-list"])
def test_the_layer_plan_comes_from_layer_types(cfg, plan):
    """Layers from 0 as published: which attention from ``layer_types``, the
    expert layer in every one; the published depth of 28 is one scanned body
    of four layers."""
    kinds = mellum.layer_kinds(cfg)
    assert len(kinds) == cfg.n_layers and {k[1] for k in kinds} == {"moe"}
    assert layer_plan(kinds) == plan


def _count(cfg):
    shapes = jax.eval_shape(lambda k: mellum.init_params(k, cfg), jax.random.PRNGKey(0))
    return {g: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes[g])) for g in shapes}


def test_the_tree_counts_the_published_parameters():
    """12,149,923,072 with all 64 experts held (the published 12B), of which
    a token meets 2,439,060,736 (the published A2.5B: 8 of a layer's 64
    experts); the cut's 538,531,072 (595,154,176 at a quarter of the
    vocabulary)."""
    whole = _count(dataclasses.replace(mellum.MellumConfig(), held_experts=tuple(range(64))))
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 128 + 2304
    assert whole["local"] == 21 * attention and whole["global"] == 7 * attention
    expert = 3 * 2304 * 896
    assert whole["moe"] == 28 * (2304 + 2304 * 64 + 64 * expert)
    assert whole["embed"] == whole["head"] == 98304 * 2304 and whole["final_norm"] == 2304
    assert sum(whole.values()) == 12_149_923_072
    assert sum(whole.values()) - 28 * (64 - 8) * expert == 2_439_060_736
    cut = _count(mellum.MellumConfig(n_layers=4, vocab_size=12288))
    assert sum(cut.values()) == 538_531_072
    assert cut["moe"] + cut["local"] + cut["global"] == 4 * 120_476_416
    assert sum(_count(mellum.MellumConfig(n_layers=4, vocab_size=24576)).values()) == 595_154_176
    shapes = jax.eval_shape(lambda k: mellum.init_params(k, mellum.MellumConfig(n_layers=4, vocab_size=12288)),
                            jax.random.PRNGKey(0))
    assert shapes["moe"]["w_gate"].shape == (4, 16, 2304, 896) and shapes["moe"]["w_down"].shape == (4, 16, 896, 2304)
    assert shapes["moe"]["router"].shape == (4, 2304, 64) and not any(n.startswith("shared") for n in shapes["moe"])
    assert sorted(shapes["local"]) == ["input_norm", "k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert shapes["local"]["wq"].shape == (3, 2304, 4096) and shapes["global"]["wk"].shape == (1, 2304, 512)


# ---- the two rotary tables ----------------------------------------------------

def _table_by_loop(rule, head_dim):
    """The rule's equations, a pair at a time."""
    out = []
    if rule.rope_type == "yarn":
        def c(n):
            return head_dim * math.log(rule.original_length / (2 * math.pi * n)) / (2 * math.log(rule.theta))
        low, high = max(math.floor(c(rule.beta_fast)), 0), min(math.ceil(c(rule.beta_slow)), head_dim - 1)
    for i in range(head_dim // 2):
        plain = rule.theta ** (-2 * i / head_dim)
        if rule.rope_type == "default":
            out.append(plain)
            continue
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain * ((1 - ramp) + ramp / rule.factor))
    return np.array(out), (low, high) if rule.rope_type == "yarn" else None


def test_both_rotary_tables_are_the_rules_equations():
    """At the published sizes: pairs 0-18 of a global layer turn as published,
    pairs 35-63 sixteen times slower, a linear ramp between; cos and sin are
    multiplied by ``attention_factor`` = 0.1 ln 16 + 1 there and by nothing in
    a window layer, whose table is ``transformer._rope``'s at theta 500000."""
    cfg = mellum.MellumConfig()
    tables = mellum.rope_tables(cfg)
    assert set(tables) == {"local", "global"}
    want_local, _ = _table_by_loop(cfg.rope_local, 128)
    want_global, (low, high) = _table_by_loop(cfg.rope_global, 128)
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(tables["local"][0], want_local, rtol=1e-6)
    np.testing.assert_allclose(tables["global"][0], want_global, rtol=1e-6)
    assert tables["local"][0].dtype == tables["global"][0].dtype == np.float32
    assert tables["local"][1] == 1.0
    assert tables["global"][1] == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    ratio = tables["global"][0] / tables["local"][0]
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)
    assert np.all(np.diff(ratio[18:36]) < 0), "a ramp between"
    np.testing.assert_allclose(ratio[19], 1 - (1 / 17) * (15 / 16), rtol=1e-6)
    # the window table is the other families' rotary
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 2, 128))
    angles = jnp.arange(96, dtype=jnp.float32)[:, None] * jnp.asarray(tables["local"][0])[None, :]
    np.testing.assert_allclose(np.asarray(_rotate(x, angles)), np.asarray(_rope(x, jnp.arange(96), 500000.0)),
                               rtol=1e-5, atol=5e-5)   # float32 angles of up to 95 radians
    # the table does not depend on the row's length
    with pytest.raises(ValueError, match="rope_type"):
        mellum.rope_tables(dataclasses.replace(cfg, rope_global=mellum.RopeRule("dynamic")))


def test_the_factor_multiplies_cos_and_sin_on_queries_and_keys():
    """A global layer's rotated heads are ``attention_factor`` times as long
    as a window layer's, so its logits are the factor's square sharper."""
    p = _attention_leaves(TINY)
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    bare = dataclasses.replace(TINY, rope_global=dataclasses.replace(TINY.rope_global, attention_factor=1.0))
    assert float(jnp.abs(mellum._attention(h, p, TINY, "global") - mellum._attention(h, p, bare, "global")).max()) > 1e-3
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 2, 8))
    inv_freq, scale = mellum.rope_tables(TINY)["global"]
    angles = jnp.arange(24, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(_rotate(x, angles, scale), axis=-1)),
                               scale * np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)


# ---- attention ----------------------------------------------------------------

def _attention_leaves(cfg, seed=3):
    params = mellum.init_params(jax.random.PRNGKey(seed), dataclasses.replace(cfg, n_layers=4))
    p = jax.tree_util.tree_map(lambda w: w[0], params["global"])
    # norms that are not ones, so that a norm left out shows
    return dict(p, q_norm=1.0 + 0.1 * jnp.arange(cfg.head_dim, dtype=jnp.float32),
                k_norm=1.0 - 0.05 * jnp.arange(cfg.head_dim, dtype=jnp.float32))


def _plain_attention(h, p, cfg, kind):
    """The layer's equations a head and a query at a time."""
    b, t, _ = h.shape
    nh, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rule = cfg.rope_local if kind == "local" else cfg.rope_global
    inv_freq, _ = _table_by_loop(rule, dh)
    factor = rule.attention_factor if rule.rope_type == "yarn" else 1.0

    def rms(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps) * w

    def rope(x):  # [t, heads, dh]
        angles = np.arange(t)[:, None] * inv_freq[None, :]
        cos, sin = np.cos(angles)[:, None, :] * factor, np.sin(angles)[:, None, :] * factor
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)

    h, p = np.asarray(h, np.float64), {n: np.asarray(w, np.float64) for n, w in p.items()}
    out = np.zeros((b, t, nh * dh))
    for row in range(b):
        q = rope(rms((h[row] @ p["wq"]).reshape(t, nh, dh), p["q_norm"]))
        k = rope(rms((h[row] @ p["wk"]).reshape(t, nkv, dh), p["k_norm"]))
        v = (h[row] @ p["wv"]).reshape(t, nkv, dh)
        for head in range(nh):
            kv = head // (nh // nkv)
            for i in range(t):
                first = max(0, i - cfg.sliding_window + 1) if kind == "local" else 0
                s = k[first:i + 1, kv] @ q[i, head] / math.sqrt(dh)
                w = np.exp(s - s.max())
                out[row, i, head * dh:(head + 1) * dh] = (w / w.sum()) @ v[first:i + 1, kv]
    return out @ p["wo"]


@pytest.mark.parametrize("kind", ["local", "global"])
def test_attention_is_the_layers_equations(kind):
    p = _attention_leaves(TINY)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 32))
    np.testing.assert_allclose(np.asarray(mellum._attention(h, p, TINY, kind)), _plain_attention(h, p, TINY, kind),
                               rtol=2e-4, atol=2e-5)


def test_the_kinds_differ_by_their_table_and_their_window():
    p = _attention_leaves(TINY)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 32))
    local, glob = mellum._attention(h, p, TINY, "local"), mellum._attention(h, p, TINY, "global")
    # position 0 sees itself alone: no table and no window shows there
    np.testing.assert_allclose(np.asarray(local[:, 0]), np.asarray(glob[:, 0]), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(local - glob)[:, 1:].max()) > 1e-3
    # inside the window the two differ by the table alone
    same_table = dataclasses.replace(TINY, rope_global=TINY.rope_local)
    np.testing.assert_allclose(np.asarray(mellum._attention(h, p, same_table, "global")[:, :16]),
                               np.asarray(local[:, :16]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["local", "global"])
def test_attention_through_the_flash_kernels_is_dense_attention(kind):
    """32 / 4 heads of 128 at a small hidden size: grouped eight to one, the
    q / k norms and the kind's table (the scaled one in the global layer)
    before the kernels, the published window of 1024 inside rows of 1280, the
    kernels interpreted, gradients of every leaf."""
    cfg = dataclasses.replace(TINY, d_model=64, n_heads=8, n_kv_heads=1, head_dim=128, sliding_window=1024,
                              rope_global=mellum.MellumConfig().rope_global)
    p = _attention_leaves(cfg)
    assert p["wq"].shape == (64, 1024) and p["wk"].shape == (64, 128)
    t = 1280 if kind == "local" else 256
    h = jax.random.normal(jax.random.PRNGKey(1), (1, t, 64))

    def out(impl, h, p):
        return mellum._attention(h, p, dataclasses.replace(cfg, attn_impl=impl), kind)

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


def test_the_window_walks_tiles_of_512_at_the_published_sizes():
    """``ops/flash_attention.py`` caps a windowed call's tile at half the
    window: 512 at this model's 1024 (Trinity's 2048 gives 1024), a band of
    three tiles of which the diagonal and the older edge are cut."""
    from torchft_tpu.ops import flash_attention

    assert flash_attention._tiles(8192, 8192, 128, 1024) == (512, 512, 3)
    assert flash_attention._tiles(8192, 8192, 128, 2048)[:2] == (1024, 1024)


# ---- the softmax router -------------------------------------------------------

def _moe_leaves(cfg, seed=8):
    return jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(seed), cfg, 1))


def test_the_softmax_router_is_a_loop_over_tokens():
    """``p = softmax`` over all 16 logits, the 4 largest, ``g = p[chosen] /
    sum p[chosen]``: no epsilon, no bias, no scale."""
    cfg = TINY.moe()
    assert (cfg.score, cfg.shared, cfg.renorm_eps, cfg.routed_scale) == ("softmax", False, 0.0, 1.0)
    p = _moe_leaves(cfg)
    flat = jax.random.normal(jax.random.PRNGKey(9), (50, 32))
    chosen, weights = moe.route_softmax(flat, p["router"], cfg)
    logits = np.asarray(flat, np.float64) @ np.asarray(p["router"], np.float64)
    for n in range(50):
        prob = np.exp(logits[n] - logits[n].max())
        prob /= prob.sum()
        best = np.argsort(-prob)[:4]
        assert sorted(best) == sorted(np.asarray(chosen[n]))
        want = {e: prob[e] / prob[best].sum() for e in best}
        for e, w in zip(np.asarray(chosen[n]), np.asarray(weights[n])):
            assert w == pytest.approx(want[int(e)], rel=1e-5)


def test_the_routers_gradient_reaches_the_columns_of_experts_held_elsewhere():
    """Every one of the 16 columns: a held expert's weight is its probability
    over the chosen experts' sum, and most of the chosen live elsewhere.
    (Over one token the probabilities left unchosen cancel between numerator
    and normaliser: a column moves by the tokens that chose it.)"""
    cfg = dataclasses.replace(TINY, held_experts=(0, 1)).moe()
    p = _moe_leaves(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 32))
    g = jax.grad(lambda p: (moe.held_moe_ffn(x, p, cfg)[0] ** 2).sum())(p)
    per_column = np.abs(np.asarray(g["router"])).max(axis=0)
    assert per_column.shape == (16,) and np.all(per_column > 1e-6)
    # one token's row of the router's Jacobian: zero on the columns it did not choose
    one = x[0, :1]
    chosen, _ = moe.route_softmax(one, p["router"], cfg)
    jac = jax.jacobian(lambda r: moe.route_softmax(one, r, cfg)[1])(p["router"])   # [1, k, d, 16]
    moved = np.abs(np.asarray(jac)).max(axis=(0, 1, 2))
    assert np.all(moved[np.asarray(chosen[0])] > 1e-4)
    assert np.all(moved[np.setdiff1d(np.arange(16), np.asarray(chosen[0]))] < 1e-6)


def _uncut_layer(x, p, top_k):
    """The whole layer, every expert on every token with the weights as a
    mask; no shared expert."""
    flat = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(flat @ p["router"], axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    weight = picked / picked.sum(-1, keepdims=True)
    out = jnp.zeros_like(flat)
    for e in range(p["w_gate"].shape[0]):
        glu = (jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e])) @ p["w_down"][e]
        out = out + jnp.where(chosen == e, weight, 0.0).sum(-1, keepdims=True) * glu
    return out.reshape(x.shape)


def test_the_four_shares_add_up_to_the_uncut_layer_and_nothing_is_counted_once():
    """The deployment's cut: 64 experts scored, 8 a token, 16 held by each of
    4 chips (ids 0-15, 16-31, 32-47, 48-63).  The four shares' outputs, simply
    added (nothing is shared, so nothing is counted once), are the uncut
    layer; every assignment lands on one share; a token none of whose experts
    lives on a share gets exactly zero from it."""
    d, f, n_routed, top_k = 32, 12, 64, 8
    model = dataclasses.replace(TINY, d_model=d, d_expert=f, n_routed_experts=n_routed, experts_per_token=top_k)
    whole = dataclasses.replace(model, held_experts=tuple(range(n_routed))).moe()
    full = _moe_leaves(whole)
    assert sorted(full) == ["router", "w_down", "w_gate", "w_up"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 96, d))
    _, chosen = jax.lax.top_k(jax.nn.softmax(x.reshape(-1, d) @ full["router"], axis=-1), top_k)
    total, landed, unrouted = 0.0, 0, []
    for share in range(4):
        held = tuple(range(16 * share, 16 * share + 16))
        cfg = dataclasses.replace(model, held_experts=held).moe()
        assert (cfg.n_routed, cfg.top_k, cfg.held, cfg.shared, cfg.score) == (64, 8, held, False, "softmax")
        mine = dict(full, **{name: full[name][np.asarray(held)] for name in ("w_gate", "w_up", "w_down")})
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        nowhere = np.asarray(((chosen < held[0]) | (chosen > held[-1])).all(-1))
        assert int(stats["unrouted"]) == int(nowhere.sum())
        assert np.all(np.asarray(y).reshape(-1, d)[nowhere] == 0.0), "no expert here, nothing from the FFN"
        assert np.all(np.abs(np.asarray(y).reshape(-1, d)[~nowhere]).max(-1) > 0)
        total = total + y
        landed += int(stats["assignments"].sum())
        unrouted.append(int(stats["unrouted"]))
    assert landed == 2 * 96 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(_uncut_layer(x, full, top_k)), rtol=2e-4, atol=2e-5)
    # under uniform routing C(48, 8) / C(64, 8) = 8.5 % of the tokens find none of theirs on a share
    assert math.comb(48, 8) / math.comb(64, 8) == pytest.approx(0.0852, abs=1e-3)
    assert 0 < sum(unrouted) and np.mean(unrouted) / 192 < 0.3


def test_a_share_normalised_over_its_own_experts_is_another_layer():
    """The normaliser is over all the chosen, those that live elsewhere too:
    a share that renormalised over the experts it holds would give every
    routed token weights that sum to one here, and the shares would not add
    up."""
    cfg = TINY.moe()
    p = _moe_leaves(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, 32))
    flat = x.reshape(-1, 32)
    chosen, weights = moe.route_softmax(flat, p["router"], cfg)
    here = np.asarray(chosen) < 8
    summed = np.where(here, np.asarray(weights), 0.0).sum(-1)
    some = here.any(-1) & ~here.all(-1)
    assert some.any() and np.all(summed[some] < 1.0 - 1e-4)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)


# ---- the whole model against the plain reference ------------------------------

def _rule_sizes(rule):
    if rule.rope_type == "default":
        return {"rope_type": "default", "rope_theta": rule.theta}
    return {"rope_type": "yarn", "rope_theta": rule.theta, "factor": rule.factor,
            "original_max_position_embeddings": rule.original_length, "beta_fast": rule.beta_fast,
            "beta_slow": rule.beta_slow, "attention_factor": rule.attention_factor}


def _reference_sizes(cfg):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "sliding_window": cfg.sliding_window,
        "layer_types": [cfg.layer_types[i % len(cfg.layer_types)] for i in range(cfg.n_layers)],
        "rope_parameters": {"sliding_attention": _rule_sizes(cfg.rope_local),
                            "full_attention": _rule_sizes(cfg.rope_global)},
        "num_experts_per_tok": cfg.experts_per_token, "held_expert_ids": list(cfg.held_experts)}


def _both_sides(cfg, sizes=None, t=None):
    from benchmarks.reference.mellum import loss_fn as reference_loss

    t = t or (128 if cfg.attn_impl == "flash" else 96)
    params = mellum.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, t), 0, cfg.vocab_size)
    got = mellum.make_grad_step(cfg)(params, tokens)
    want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, sizes or _reference_sizes(cfg), None)))(params, tokens)
    return got, want


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=8, held_experts=(3, 8, 9, 15)),
    dataclasses.replace(TINY, n_layers=5, remat=False, held_experts=tuple(range(16))),
    dataclasses.replace(TINY, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, remat_policy="dots",
                        layer_types=("full_attention", "sliding_attention")),
    dataclasses.replace(TINY, n_layers=4, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64, sliding_window=48,
                        attn_impl="flash"),
], ids=["the-cuts-four-layers", "two-scanned-periods", "every-expert-held-no-remat", "one-kv-head-another-list",
        "through-the-flash-kernels"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Loss and every gradient leaf on seeded weights; the reference blends
    two tables where the program has one closed form, forms ``[T, T]`` scores
    a head at a time and runs the experts one at a time."""
    (loss, grads), (want, want_grads) = _both_sides(cfg)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == 22
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape and r.size
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("fault", ["no attention_factor", "normalised over the held"])
def test_the_agreement_needs_the_rotarys_scale_and_the_routers_normaliser(fault):
    """The tolerance of the test above is tight enough to tell: a reference
    whose global layers drop ``attention_factor``, or a program whose share
    normalises over the experts it holds instead of all the chosen, is out of
    it by far."""
    cfg = TINY
    sizes = _reference_sizes(cfg)
    if fault == "no attention_factor":
        sizes["rope_parameters"]["full_attention"]["attention_factor"] = 1.0
        (loss, grads), (want, want_grads) = _both_sides(cfg, sizes)
    else:
        real = moe.route_softmax

        def over_the_held(flat, router, c):
            chosen, weights = real(flat, router, c)
            here = jnp.isin(chosen, jnp.asarray(c.held))
            return chosen, weights / jnp.maximum(jnp.where(here, weights, 0.0).sum(-1, keepdims=True), 1e-9)

        moe.route_softmax = over_the_held
        try:
            (loss, grads), (want, want_grads) = _both_sides(cfg)
        finally:
            moe.route_softmax = real
    worst = max(
        float(np.abs(np.asarray(g) - np.asarray(r)).max() / np.abs(np.asarray(r)).max())
        for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)))
    assert abs(float(loss) - float(want)) > 2e-5 * abs(float(want)) or worst > 2e-2
    assert worst > 2e-3, "the stated tolerance, 2e-3 of a leaf's largest entry, fails"


def test_the_reference_reads_the_lower_precision_control():
    """With float8 operands the reference's loss moves, with bfloat16 less:
    the knob reaches every product."""
    from benchmarks.reference.mellum import loss_fn as reference_loss

    params = mellum.init_params(jax.random.PRNGKey(5), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, TINY.vocab_size)
    sizes = _reference_sizes(TINY)
    exact, half, eighth = (float(jax.jit(lambda p, d=d: reference_loss(p, tokens, sizes, d))(params))
                           for d in (None, "bfloat16", "float8_e4m3fn"))
    assert 0 < abs(half - exact) < abs(eighth - exact) < 0.2 * exact


def test_the_references_tables_are_the_programs():
    """Written apart (a blend of the plain and the slowed frequencies against
    one closed form), equal to rounding, at the published sizes."""
    from benchmarks.reference.mellum import rope_table

    cfg = mellum.MellumConfig()
    for kind, rule in (("local", cfg.rope_local), ("global", cfg.rope_global)):
        inv_freq, factor = rope_table(_rule_sizes(rule), 128)
        np.testing.assert_allclose(mellum.rope_tables(cfg)[kind][0], inv_freq, rtol=1e-6)
        assert mellum.rope_tables(cfg)[kind][1] == factor


def test_logits_and_loss_agree():
    params = mellum.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, TINY.vocab_size)
    logits = mellum.forward(params, tokens, TINY)
    assert logits.shape == (2, 64, 128) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    assert float(mellum.loss_fn(params, tokens, TINY)) == pytest.approx(float(want), rel=1e-5)


def test_bfloat16_compute_keeps_float32_parameters_and_gradients():
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    params = mellum.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, cfg.vocab_size)
    loss, grads = mellum.make_grad_step(cfg)(params, tokens)
    assert loss.dtype == jnp.float32 and np.isfinite(float(loss))
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))
    exact, _ = mellum.make_grad_step(TINY)(params, tokens)
    assert abs(float(loss) - float(exact)) < 0.02 * float(exact)


# ---- routing stats and the counters, more experts held than a token chooses ----

def _read(name, **labels):
    from torchft_tpu.utils import metrics

    samples = metrics.parse_text_exposition(metrics.REGISTRY.render()).get(name, {"samples": {}})["samples"]
    return {(n, tuple(sorted(l))): v for (n, l), v in samples.items()}.get(
        (name, tuple(sorted(labels.items()))), 0.0)


def test_routing_stats_over_both_shares_count_every_assignment():
    """8 of 16 held and 4 a token: a token lands on a share up to four times;
    the two shares' first layers hold every one of the batch's assignments."""
    params = mellum.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, TINY.vocab_size)
    landed = 0
    for share in range(2):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(8 * share, 8 * share + 8)))
        stats = mellum.make_routing_stats(cfg)(params, tokens)
        assert stats["assignments"].shape == (4, 8) and stats["unrouted"].shape == (4,)
        landed += int(stats["assignments"][0].sum())   # layer 0's input is the same on every share
        if share == 0:
            assert int(stats["assignments"][0].sum()) > tokens.size - int(stats["unrouted"][0]), \
                "some token landed here more than once"
    assert landed == tokens.size * TINY.experts_per_token


def test_routing_stats_feed_the_shared_counters_under_the_models_layer_numbers():
    """Through ``models/moe.py`` ``record_routing_stats``, as the other sparse
    families: every layer has experts, so the rows are layers 0-3, experts by
    their published id; the assignments counter counts one token up to
    ``experts_per_token`` times in a layer."""
    cfg = dataclasses.replace(TINY, held_experts=(0, 1, 2, 3, 4, 5, 11, 12))
    params = mellum.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
    stats = mellum.make_routing_stats(cfg)(params, tokens)
    layers = (0, 1, 2, 3)
    keys = [(row, layer, slot, e) for row, layer in enumerate(layers) for slot, e in enumerate(cfg.held_experts)]
    before = [_read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) for _, layer, _, e in keys]
    lost = [_read("torchft_moe_tokens_unrouted_total", layer=str(layer)) for layer in layers]
    mellum.record_routing_stats(stats, cfg)
    for (row, layer, slot, e), was in zip(keys, before):
        assert _read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) - was == int(
            stats["assignments"][row, slot])
    for row, (layer, was) in enumerate(zip(layers, lost)):
        assert _read("torchft_moe_tokens_unrouted_total", layer=str(layer)) - was == int(stats["unrouted"][row])
    assert int(stats["assignments"].sum(-1).max()) > tokens.size, "more assignments than tokens in one layer"


def test_the_step_keeps_the_flash_forwards_results_and_opens_the_models_scopes():
    """Full remat through ``transformer._remat``: every layer's flash forward
    is kept (the gauge reads their bytes); the lowered program names the
    scopes the per-layer metrics read, and no ``moe.shared``."""
    cfg = dataclasses.replace(TINY, n_layers=4, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64, sliding_window=48,
                              attn_impl="flash", dtype=jnp.bfloat16)
    params = mellum.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, cfg.vocab_size)
    step = mellum.make_grad_step(cfg)
    text = step.lower(params, tokens).as_text(debug_info=True)
    kept = _read("torchft_remat_kept_bytes")
    assert kept == 4 * (2 * 128 * 2 * 64 * 2 + 2 * 2 * 128 * 4), "a layer's B T H Dv x 2 B + B H T x 4 B, four layers"
    for scope in ("embed", "attn.proj", "attn.local", "attn.global", "attn.rope", "moe.route", "moe.route.score",
                  "moe.route.place", "moe.experts", "moe.gathered", "moe.masked", "head"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert "moe.shared" not in text and "ffn.dense" not in text


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    """The model's gradient tree at a small size: 22 leaves in three stacks,
    four-dimensional expert leaves, an untied head."""
    params = mellum.init_params(jax.random.PRNGKey(11), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0, TINY.vocab_size)
    _, grads = mellum.make_grad_step(TINY)(params, tokens)
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    grads = _gradient_tree()
    leaves, tree = jax.tree_util.tree_flatten(grads)
    assert len(leaves) == 22 and max(leaf.ndim for leaf in leaves) == 4
    assert grads["moe"]["w_down"].shape == (4, 8, 16, 32), "the largest leaves: the expert stacks"
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/mellum", f"rank{r}", r, 2), range(2)))
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
    assert np.asarray(back["params"]["moe"]["w_gate"]).shape == (4, 8, 32, 16)
