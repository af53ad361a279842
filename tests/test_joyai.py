"""The JoyAI-LLM-Flash model (``models/joyai.py``) and what it brings with it:
latent attention with a query latent and a rotary part (``models/mla.py``, the
function ``models/kimi_linear.py`` runs too), the rotary of interleaved pairs
against a complex product, the multi-token-prediction module over ``T``
positions with a masked tail against its ``T - 1``-long form, the shared
expert layer at this router's widths (all 32 shares against the uncut layer),
and the whole model, both prediction depths, against the benchmark's plain
reference (``benchmarks/reference/joyai.py``, which imports nothing of the
program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import joyai, mla, moe
from torchft_tpu.models.kimi_linear import layer_plan

TINY = joyai.JoyAIConfig(
    vocab_size=128, d_model=32, n_layers=3, first_k_dense=1, n_heads=2, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0, d_ff=64, d_expert=16,
    n_routed_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32,
    attn_impl="dense")
D, M = ("mla", "dense"), ("mla", "moe")


# ---- the pattern of layers and the tree ---------------------------------------

@pytest.mark.parametrize("cfg,plan", [
    (joyai.JoyAIConfig(), [((D,), 1), ((M,), 39)]),
    (dataclasses.replace(joyai.JoyAIConfig(), n_layers=6), [((D,), 1), ((M,), 5)]),
    (TINY, [((D,), 1), ((M,), 2)]),
    (dataclasses.replace(TINY, first_k_dense=0), [((M,), 3)]),
], ids=["published-40", "cut-6", "tiny-3", "no-dense"])
def test_the_trunk_is_a_dense_layer_and_one_scan(cfg, plan):
    """Layers from 0 as published: the first dense, the others one scanned
    body; the published depth is two layer bodies and the module's, not 41."""
    assert layer_plan(joyai.layer_kinds(cfg)) == plan


def test_the_tree_is_three_stacks_and_the_module():
    params = jax.jit(lambda k: joyai.init_params(k, TINY))(jax.random.PRNGKey(0))
    assert set(params) == {"embed", "head", "final_norm", "mla", "dense", "moe", "mtp"}
    first = {g: {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(params[g])}
             for g in joyai.GROUPS + ("mtp",)}
    assert first == {"mla": {3}, "dense": {1}, "moe": {2}, "mtp": {1}}
    # the module: an attention and an expert layer under their own names, and its four
    assert set(params["mtp"]) == set(params["mla"]) | set(params["moe"]) | {
        "e_norm", "h_norm", "w_eh", "out_norm"}
    assert params["mtp"]["w_eh"].shape == (1, 64, 32) and params["mla"]["q_b"].shape == (3, 24, 2 * 24)
    assert params["mla"]["kv_a"].shape == (3, 32, 16 + 8) and "wq" not in params["mla"]
    # the published count at the cell's sizes, by the shapes alone
    cell = joyai.JoyAIConfig(vocab_size=16160, n_layers=6)
    shapes = jax.eval_shape(lambda k: joyai.init_params(k, cell), jax.random.PRNGKey(0))
    count = {g: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes[g])) for g in shapes}
    assert count["mtp"] == 77_737_984 and count["embed"] == count["head"] == 33_095_680
    assert sum(count.values()) == 561_039_360
    without = jax.eval_shape(lambda k: joyai.init_params(k, dataclasses.replace(TINY, n_predict_layers=0)),
                             jax.random.PRNGKey(0))
    assert "mtp" not in without
    with pytest.raises(ValueError, match="one layer or none"):
        joyai.init_params(jax.random.PRNGKey(0), dataclasses.replace(TINY, n_predict_layers=2))


# ---- the rotary of pairs, the shared key head, the query's latent --------------

def _complex_rotary(x, theta):
    """``x [B, T, H, rope]``: each pair (2j, 2j + 1) as a complex number
    times ``exp(i pos theta^(-2j / rope))``."""
    t, rope = x.shape[1], x.shape[-1]
    angle = np.arange(t)[:, None] * theta ** (-np.arange(0, rope, 2) / rope)[None]
    z = (np.asarray(x, np.float64)[..., 0::2] + 1j * np.asarray(x, np.float64)[..., 1::2])
    z = z * np.exp(1j * angle)[None, :, None, :]
    out = np.empty(x.shape)
    out[..., 0::2], out[..., 1::2] = z.real, z.imag
    return out


@pytest.mark.parametrize("rope,theta", [(8, 10000.0), (64, 32_000_000.0)])
def test_rotating_halves_of_reordered_columns_is_rotating_pairs(rope, theta):
    """The program reorders the weights' rotary columns (evens, then odds)
    and rotates halves; the scores are those of the complex product of pairs."""
    from torchft_tpu.models.transformer import _rope

    q = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 3, rope))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 1, rope))
    order = mla.pairs_as_halves(rope)
    assert sorted(order.tolist()) == list(range(rope)) and order[:2].tolist() == [0, 2]
    positions = jnp.arange(40)
    mine = jnp.einsum("bqhd,bkhd->bhqk", _rope(q[..., order], positions, theta),
                      jnp.broadcast_to(_rope(k[..., order], positions, theta), q.shape))
    want = np.einsum("bqhd,bkhd->bhqk", _complex_rotary(q, theta),
                     np.broadcast_to(_complex_rotary(k, theta), q.shape))
    np.testing.assert_allclose(np.asarray(mine), want, rtol=2e-4, atol=2e-4)
    # and the turned pairs themselves, put back in their places
    back = np.empty(q.shape)
    back[..., order] = np.asarray(_rope(q[..., order], positions, theta))
    np.testing.assert_allclose(back, _complex_rotary(q, theta), rtol=2e-4, atol=2e-5)


def _one_layer(**over):
    cfg = dataclasses.replace(TINY, **over)
    p = jax.tree_util.tree_map(lambda w: w[0], mla.init_mla_params(jax.random.PRNGKey(3), cfg.mla(), 1))
    return cfg.mla(), p


def _plain_latent_attention(h, p, cfg):
    """The layer's equations, one head at a time, the rotary on pairs."""
    b, t, _ = h.shape
    nope, rope, dv, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * w

    q = (rms(h @ p["q_a"], p["q_norm"]) @ p["q_b"]).reshape(b, t, cfg.n_heads, nope + rope)
    ck = h @ p["kv_a"]
    kv = (rms(ck[..., :rank], p["kv_norm"]) @ p["kv_b"]).reshape(b, t, cfg.n_heads, nope + dv)
    q_r = jnp.asarray(_complex_rotary(q[..., nope:], cfg.rope_theta), jnp.float32)
    k_r = jnp.asarray(_complex_rotary(ck[..., None, rank:], cfg.rope_theta), jnp.float32)[:, :, 0]
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None]
    heads = []
    for head in range(cfg.n_heads):
        s = q[:, :, head, :nope] @ jnp.swapaxes(kv[:, :, head, :nope], 1, 2) + q_r[:, :, head] @ jnp.swapaxes(k_r, 1, 2)
        s = jnp.where(seen, s / np.sqrt(nope + rope), -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ kv[:, :, head, nope:])
    return jnp.stack(heads, 2).reshape(b, t, cfg.n_heads * dv) @ p["wo"]


def test_latent_attention_is_the_layers_equations():
    """The query through its latent and norm, one rotated key head seen
    alike by all query heads, pairs turned by the position, the softmax at
    (nope + rope)^-0.5."""
    cfg, p = _one_layer()
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 48, 32))
    np.testing.assert_allclose(np.asarray(mla.mla_attention(h, p, cfg)),
                               np.asarray(_plain_latent_attention(h, p, cfg)), rtol=2e-4, atol=2e-5)


def test_the_rotary_carries_position_and_only_on_its_dimensions():
    """Turned off (``rope_theta`` None: the NoPE variant) the output differs,
    but not at position 0, which no angle turns; and the keys' one rotary head
    is the same for every query head: a change to it moves them all."""
    cfg, p = _one_layer()
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 32))
    with_rope = mla.mla_attention(h, p, cfg)
    without = mla.mla_attention(h, p, dataclasses.replace(cfg, rope_theta=None))
    assert float(jnp.abs(with_rope - without)[:, 1:].max()) > 1e-3
    np.testing.assert_allclose(np.asarray(with_rope[:, 0]), np.asarray(without[:, 0]), rtol=1e-5, atol=1e-6)
    # rotating halves of the columns as they lie is another model
    halves = mla.mla_attention(h, p, dataclasses.replace(cfg, rope_interleave=False))
    assert float(jnp.abs(with_rope - halves).max()) > 1e-3
    # ... and the same model once the columns are reordered by hand
    order = mla.pairs_as_halves(8)
    q_cols = np.concatenate([np.concatenate([np.arange(16), 16 + order]) + 24 * head for head in range(2)])
    by_hand = dict(p, q_b=p["q_b"][:, q_cols], kv_a=p["kv_a"][:, np.concatenate([np.arange(16), 16 + order])])
    np.testing.assert_allclose(
        np.asarray(mla.mla_attention(h, by_hand, dataclasses.replace(cfg, rope_interleave=False))),
        np.asarray(with_rope), rtol=1e-5, atol=1e-6)
    moved = dict(p, kv_a=p["kv_a"].at[:, 16:].multiply(2.0))   # the shared key head's columns
    wo_by_head = jnp.abs((mla.mla_attention(h, moved, cfg) - with_rope)).reshape(1, 32, 32)
    assert float(wo_by_head.max()) > 1e-4


def test_latent_attention_through_the_flash_kernels_is_dense_attention():
    """Queries and keys of 192 against values of 128 with the rotary part
    turned, the kernels interpreted, gradients of every leaf."""
    cfg, p = _one_layer(d_model=64, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=128,
                        qk_rope_head_dim=64, v_head_dim=128, rope_theta=32e6)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))

    def out(impl, h, p):
        return mla.mla_attention(h, p, dataclasses.replace(cfg, attn_impl=impl))

    np.testing.assert_allclose(np.asarray(out("flash", h, p)), np.asarray(out("dense", h, p)),
                               rtol=2e-4, atol=2e-5)
    g_flash = jax.grad(lambda h, p: (out("flash", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    g_dense = jax.grad(lambda h, p: (out("dense", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    for a, b in zip(jax.tree_util.tree_leaves(g_flash), jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(b).max()))


# ---- the module: two depths, a masked tail -------------------------------------

def _by_hand_parts(params, tokens, cfg):
    """Both depths' losses with the module over the ``T - 1`` positions that
    have a next token, from the program's own layer."""
    b, t = tokens.shape
    x, _ = joyai.forward_hidden(params, tokens, cfg)
    logp = jax.nn.log_softmax(joyai._logits(params, x, cfg)[:, :-1], -1)
    main = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    p = {name: leaf[0] for name, leaf in params["mtp"].items()}

    def rms(v, w):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + cfg.rms_norm_eps) * w

    h = jnp.concatenate([rms(params["embed"][tokens[:, 1:]], p["e_norm"]), rms(x[:, :-1], p["h_norm"])], -1) @ p["w_eh"]
    y, _ = joyai._make_layer(("mla", "moe"), cfg)(h, p, p)                       # [B, T - 1, E]
    logits = joyai._logits({"final_norm": p["out_norm"], "head": params["head"]}, y, cfg)[:, :-1]
    mtp = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), tokens[:, 2:, None], -1).mean()
    return main, mtp


def test_the_module_over_t_positions_with_a_masked_tail_is_its_shorter_form():
    """Position ``T - 1`` is fed some token's embedding and left out of the
    loss with position ``T - 2``: losses and every gradient are those of the
    module run over the ``T - 1`` positions that exist."""
    params = joyai.init_params(jax.random.PRNGKey(5), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 48), 0, TINY.vocab_size)
    main, mtp = joyai.make_loss_parts(TINY)(params, tokens)
    want_main, want_mtp = jax.jit(lambda p: _by_hand_parts(p, tokens, TINY))(params)
    np.testing.assert_allclose([float(main), float(mtp)], [float(want_main), float(want_mtp)], rtol=1e-5)
    assert abs(float(mtp) - float(main)) > 1e-3, "the two depths are two losses"
    loss, got = joyai.make_grad_step(TINY)(params, tokens)
    assert float(loss) == pytest.approx(float(main) + 0.3 * float(mtp), rel=1e-6)
    want = jax.jit(jax.grad(
        lambda p: sum(w * part for w, part in zip((1.0, 0.3), _by_hand_parts(p, tokens, TINY)))))(params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


def test_embedding_and_head_gather_both_depths_gradients():
    """Each is used twice in a step: its gradient is the sum of the two
    depths' paths, and without the module's loss the module's leaves get none."""
    params = joyai.init_params(jax.random.PRNGKey(7), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, TINY.vocab_size)
    _, whole = joyai.make_grad_step(TINY)(params, tokens)
    main, mtp = jax.jit(lambda p: tuple(
        jax.grad(lambda p, depth=depth: joyai.loss_parts(p, tokens, TINY)[depth])(p) for depth in (0, 1)))(params)
    for name in ("embed", "head", "final_norm"):
        want = np.asarray(main[name] + 0.3 * mtp[name])
        np.testing.assert_allclose(np.asarray(whole[name]), want, rtol=1e-3, atol=1e-5 * float(np.abs(want).max()))
    assert float(jnp.abs(mtp["head"]).max()) > 0 and float(jnp.abs(mtp["embed"]).max()) > 0
    assert float(jnp.abs(mtp["final_norm"]).max()) == 0, "the module has a final norm of its own"
    assert all(float(jnp.abs(g).max()) == 0 for g in jax.tree_util.tree_leaves(main["mtp"]))
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(mtp["mtp"]))
    # the trunk learns from the second depth too
    assert float(jnp.abs(mtp["mla"]["q_a"]).max()) > 0
    no_module = dataclasses.replace(TINY, n_predict_layers=0)
    trunk = {k: v for k, v in params.items() if k != "mtp"}
    np.testing.assert_allclose(float(jax.jit(lambda p: joyai.loss_fn(p, tokens, no_module))(trunk)),
                               float(joyai.make_loss_parts(TINY)(params, tokens)[0]), rtol=1e-6)


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


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """The deployment's cut at a small size: 256 experts scored, 8 a token, 8
    held by each of 32 chips, the scale 2.5.  What all 32 shares give, the
    shared expert counted once, is the uncut layer; every assignment lands on
    one share."""
    d, f, n_routed, top_k, shares = 32, 12, 256, 8, 32
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
        assert (cfg.n_routed, cfg.top_k, cfg.held, cfg.routed_scale) == (256, 8, held, 2.5)
        mine = dict(full, **{name: full[name][np.asarray(held)] for name in ("w_gate", "w_up", "w_down")})
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        total = total + (y - shared)
        landed += int(stats["assignments"].sum())
    assert landed == 2 * 40 * top_k
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(_uncut_layer(x, full, top_k, 2.5)),
                               rtol=2e-4, atol=2e-5)


# ---- the whole model against the plain reference ------------------------------

def _reference_sizes(cfg):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "first_k_dense_replace": cfg.first_k_dense,
        "num_experts_per_tok": cfg.experts_per_token, "held_expert_ids": list(cfg.held_experts),
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_nextn_predict_layers": cfg.n_predict_layers, "mtp_loss_weight": cfg.mtp_loss_weight}


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=6, held_experts=(3, 8, 9, 15), rope_theta=32e6),
    dataclasses.replace(TINY, n_layers=2, first_k_dense=0, remat=False, mtp_loss_weight=1.0),
    dataclasses.replace(TINY, n_layers=2, d_model=64, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=128,
                        qk_rope_head_dim=64, v_head_dim=128, attn_impl="flash"),
    dataclasses.replace(TINY, n_predict_layers=0),
], ids=["a-dense-and-two-expert-layers", "five-scanned", "no-dense-no-remat", "through-the-flash-kernels",
        "no-module"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Both depths' losses in one and every gradient leaf, the embedding's and
    the head's (two paths each) among them, on seeded weights; the reference
    runs the module over ``T - 1`` positions and turns pairs as a complex
    product."""
    from benchmarks.reference.joyai import loss_fn as reference_loss

    t = 128 if cfg.attn_impl == "flash" else 96
    params = joyai.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, t), 0, cfg.vocab_size)
    loss, grads = joyai.make_grad_step(cfg)(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, _reference_sizes(cfg), None)))(params, tokens)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == (43 if cfg.n_predict_layers else 23)
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape
        if not r.size:  # a group this pattern has no layer of
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_separates_the_two_depths():
    """With the module's weight at 0 the reference is the next-token loss
    alone; the second depth is what is left, and is the program's."""
    from benchmarks.reference.joyai import loss_fn as reference_loss

    params = joyai.init_params(jax.random.PRNGKey(5), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, TINY.vocab_size)
    sizes = _reference_sizes(TINY)
    both = float(jax.jit(lambda p: reference_loss(p, tokens, sizes))(params))
    first = float(jax.jit(lambda p: reference_loss(p, tokens, dict(sizes, mtp_loss_weight=0.0)))(params))
    main, mtp = joyai.make_loss_parts(TINY)(params, tokens)
    assert first == pytest.approx(float(main), rel=2e-5)
    assert (both - first) / 0.3 == pytest.approx(float(mtp), rel=2e-4)


def test_logits_and_loss_agree():
    params = joyai.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, TINY.vocab_size)
    logits = jax.jit(lambda p: joyai.forward(p, tokens, TINY))(params)
    assert logits.shape == (2, 64, TINY.vocab_size) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(float(joyai.make_loss_parts(TINY)(params, tokens)[0]), float(want), rtol=1e-5)


def test_bfloat16_compute_keeps_float32_parameters_and_gradients():
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    params = joyai.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, cfg.vocab_size)
    loss, grads = joyai.make_grad_step(cfg)(params, tokens)
    want = jax.jit(lambda p: joyai.loss_fn(p, tokens, TINY))(params)
    assert loss.dtype == jnp.float32 and abs(float(loss) - float(want)) < 0.02 * float(want)
    assert all(g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_the_router_bias_is_a_buffer_with_a_row_for_the_module():
    """Rows: the trunk's expert layers, then the module's; it moves the
    choice and takes no gradient."""
    params = joyai.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, TINY.vocab_size)
    zeros = jnp.zeros((3, 16))
    base = joyai.make_routing_stats(TINY)(params, tokens)
    same = joyai.make_routing_stats(TINY, zeros)(params, tokens)
    np.testing.assert_array_equal(np.asarray(base["assignments"]), np.asarray(same["assignments"]))
    pushed = joyai.make_routing_stats(TINY, zeros.at[2, 0].set(10.0))(params, tokens)
    np.testing.assert_array_equal(np.asarray(pushed["assignments"][:2]), np.asarray(base["assignments"][:2]))
    assert int(pushed["assignments"][2, 0]) == tokens.size, "every token of the module's layer now picks expert 0"
    grad = jax.jit(jax.grad(lambda bias: joyai.loss_fn(params, tokens, TINY, bias)))(zeros)
    assert float(jnp.abs(grad).max()) == 0.0


# ---- routing stats, the two depths' gauge --------------------------------------

def _read(name, **labels):
    from torchft_tpu.utils import metrics

    samples = metrics.parse_text_exposition(metrics.REGISTRY.render()).get(name, {"samples": {}})["samples"]
    return {(n, tuple(sorted(l))): v for (n, l), v in samples.items()}.get(
        (name, tuple(sorted(labels.items()))), 0.0)


def test_routing_stats_over_all_shares_count_every_assignment():
    params = joyai.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, TINY.vocab_size)
    landed = 0
    for share in range(4):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(4 * share, 4 * share + 4)))
        stats = joyai.make_routing_stats(cfg)(params, tokens)
        # two expert layers of the trunk, then the module's
        assert stats["assignments"].shape == (3, 4) and stats["unrouted"].shape == (3,)
        # the layers before the first expert layer are the same on every share
        landed += int(stats["assignments"][0].sum())
    assert landed == tokens.size * TINY.experts_per_token


def test_routing_stats_feed_the_shared_counters_the_module_under_its_published_number():
    """Through ``models/moe.py`` ``record_routing_stats``, as the other sparse
    families: layers from 0, experts by their published id; the module's
    layer is layer 40 of the published model, layer 3 of this trunk."""
    cfg = dataclasses.replace(TINY, held_experts=(2, 5, 11, 12))
    params = joyai.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
    stats = joyai.make_routing_stats(cfg)(params, tokens)
    for module_layer, layers in ((40, (1, 2, 40)), (None, (1, 2, 3))):
        keys = [(row, layer, slot, e) for row, layer in enumerate(layers) for slot, e in enumerate(cfg.held_experts)]
        before = [_read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) for _, layer, _, e in keys]
        lost = [_read("torchft_moe_tokens_unrouted_total", layer=str(layer)) for layer in layers]
        joyai.record_routing_stats(stats, cfg, module_layer)
        for (row, layer, slot, e), was in zip(keys, before):
            assert _read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) - was == int(
                stats["assignments"][row, slot])
        for row, (layer, was) in enumerate(zip(layers, lost)):
            assert _read("torchft_moe_tokens_unrouted_total", layer=str(layer)) - was == int(stats["unrouted"][row])


def test_the_two_depths_losses_feed_their_gauge():
    params = joyai.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, TINY.vocab_size)
    parts = joyai.make_loss_parts(TINY)(params, tokens)
    main, mtp = parts
    assert float(main) + 0.3 * float(mtp) == pytest.approx(float(joyai.make_grad_step(TINY)(params, tokens)[0]), rel=1e-6)
    joyai.record_loss_parts(parts, "joyai_test_replica")
    assert _read("torchft_loss_depth", replica_id="joyai_test_replica", depth="0") == pytest.approx(float(main))
    assert _read("torchft_loss_depth", replica_id="joyai_test_replica", depth="1") == pytest.approx(float(mtp))


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    """The model's gradient tree at a small size: 43 leaves in five groups
    (the module's stacked ``[1, ...]``), last dimensions of 24 (``kv_a``,
    ``q_a``), a four-dimensional expert leaf."""
    params = joyai.init_params(jax.random.PRNGKey(11), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0, TINY.vocab_size)
    _, grads = joyai.make_grad_step(TINY)(params, tokens)
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    leaves, tree = jax.tree_util.tree_flatten(_gradient_tree())
    assert len(leaves) == 43 and max(leaf.ndim for leaf in leaves) == 4
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/joyai", f"rank{r}", r, 2), range(2)))
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
