"""The Kimi Linear model (``models/kimi_linear.py``) and what it brings with it:
the chip's share of the expert layer against the uncut layer (the chunked
delta rule against its recurrence is ``tests/test_kda.py``), dropless routing under a skewed router, latent
attention through the flash kernels, the layer plan, and the whole model
against the benchmark's plain reference (``benchmarks/reference/kimi_linear.py``,
which imports nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import kimi_linear as kl
from torchft_tpu.models import moe

TINY = kl.KimiLinearConfig(
    vocab_size=128, d_model=32, n_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
    first_k_dense=1, n_heads=2, kda_heads=2, kda_head_dim=16, kda_gate_rank=16, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=64, d_expert=16,
    n_routed_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32,
    attn_impl="dense")


# ---- (c), (d) the chip's share of the expert layer ---------------------------

def _expert_layer(held, slack=2.0, n_routed=16, top_k=4, d=32, f=16, seed=0):
    cfg = moe.HeldMoEConfig(d_model=d, d_expert=f, n_routed=n_routed, top_k=top_k, held=tuple(held),
                            routed_scale=2.446, slack=slack, dtype=jnp.float32)
    whole = moe.HeldMoEConfig(d_model=d, d_expert=f, n_routed=n_routed, top_k=top_k,
                              held=tuple(range(n_routed)), dtype=jnp.float32)
    full = jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(seed), whole, 1))
    mine = dict(full)
    for name in ("w_gate", "w_up", "w_down"):
        mine[name] = full[name][np.asarray(held)]
    return cfg, mine, full


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


@pytest.mark.parametrize("shares", [4, 2, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """What all the chips that share a layer give, the shared expert counted
    once, is the uncut layer; the assignments that landed sum to N k."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 48, 32))
    n_routed, top_k = 16, 4
    per = n_routed // shares
    total, landed, shared = 0.0, 0, None
    for s in range(shares):
        cfg, mine, full = _expert_layer(range(s * per, (s + 1) * per))
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        flat = x.reshape(-1, 32)
        only_shared = ((jax.nn.silu(flat @ mine["shared_gate"]) * (flat @ mine["shared_up"]))
                       @ mine["shared_down"]).reshape(x.shape)
        shared = only_shared
        total = total + (y - only_shared)
        landed += int(stats["assignments"].sum())
        assert 0 <= int(stats["unrouted"]) <= 96
    assert landed == 2 * 48 * top_k
    want = _uncut_layer(x, full, top_k, 2.446)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("slack,path", [(8.0, "gathered rows"), (0.01, "every expert on every token")])
def test_a_skewed_router_drops_nothing(slack, path):
    """Every token prefers the experts held here: each gets 96 assignments
    where the mean load is 24.  A pool of eight times the mean holds them;
    one of a hundredth does not and the layer takes the masked path; either
    way the result is the uncut layer's part and every gradient flows."""
    cfg, mine, full = _expert_layer((0, 1, 2, 3), slack=slack)
    skew = jnp.zeros((32, 16)).at[:, :4].set(0.0).at[:, 4:].set(-5.0)
    mine, full = {**mine, "router": skew + 0.01 * mine["router"]}, {**full, "router": skew + 0.01 * full["router"]}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 32)) + 0.5
    y, stats = moe.held_moe_ffn(x, mine, cfg)
    assert stats["assignments"].tolist() == [96, 96, 96, 96] and int(stats["unrouted"]) == 0
    only_held = {**full, **{k: full[k].at[4:].set(0.0) for k in ("w_down",)}}
    np.testing.assert_allclose(np.asarray(y), np.asarray(_uncut_layer(x, only_held, 4, 2.446)),
                               rtol=2e-4, atol=2e-5)
    grads = jax.grad(lambda p: moe.held_moe_ffn(x, p, cfg)[0].sum())(mine)
    want = jax.grad(lambda p: _uncut_layer(x, {**p, "w_down": p["w_down"].at[4:].set(0.0)}, 4, 2.446).sum())(full)
    for name in ("router", "shared_up"):
        np.testing.assert_allclose(np.asarray(grads[name]), np.asarray(want[name]), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w_gate"]), np.asarray(want["w_gate"][:4]), rtol=1e-3, atol=1e-5)


def test_the_router_bias_moves_the_choice_and_not_the_weights():
    cfg, mine, _ = _expert_layer((0, 1, 2, 3))
    flat = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    chosen, weights = moe.route_sigmoid(flat, mine["router"], cfg)
    bias = jnp.zeros((16,)).at[9].set(10.0)
    chosen_b, weights_b = moe.route_sigmoid(flat, mine["router"], cfg, bias)
    assert bool((chosen_b == 9).any(axis=-1).all()) and not bool((chosen == 9).any(axis=-1).all())
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.446, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights_b.sum(-1)), 2.446, rtol=1e-5)
    assert float(weights_b.max()) < 2.446  # the bias is in no weight


# ---- (e) latent attention through the flash kernels --------------------------

@pytest.mark.parametrize("over", [{}, {"q_lora_rank": 48, "mla_use_nope": False}],
                         ids=["as-published", "a-query-latent-and-rotary"])
def test_latent_attention_through_the_flash_kernels_is_dense_attention(over):
    """Queries and keys of 192 against values of 128, the kernels interpreted;
    as published (a full-rank query, no rotary) and with the two keys the
    shared function (``models/mla.py``) reads set the other way."""
    cfg = dataclasses.replace(
        TINY, d_model=64, n_heads=2, kv_lora_rank=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_layers=1, kda_layers=(), full_attn_layers=(1,), **over)
    params = kl.init_params(jax.random.PRNGKey(0), cfg)
    assert ("wq" in params["mla"]) == (not over) and ("q_b" in params["mla"]) == bool(over)
    p = jax.tree_util.tree_map(lambda w: w[0], params["mla"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))

    def out(impl, h, p):
        return kl._mla_attention(h, p, dataclasses.replace(cfg, attn_impl=impl))

    np.testing.assert_allclose(np.asarray(out("flash", h, p)), np.asarray(out("dense", h, p)),
                               rtol=2e-4, atol=2e-5)
    g_flash = jax.grad(lambda h, p: (out("flash", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    g_dense = jax.grad(lambda h, p: (out("dense", h, p) ** 2).sum(), argnums=(0, 1))(h, p)
    for a, b in zip(jax.tree_util.tree_leaves(g_flash), jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(b).max()))


# ---- the pattern of layers ---------------------------------------------------

K, M, D = ("kda", "moe"), ("mla", "moe"), ("kda", "dense")


@pytest.mark.parametrize("cfg,plan", [
    (kl.KimiLinearConfig(), [((D,), 1), ((K, K, M, K), 6), ((K, M), 1)]),
    (TINY, [((D,), 1), ((K,), 2), ((M, K), 1)]),
    (dataclasses.replace(TINY, n_layers=9, kda_layers=(1, 2, 3, 5, 6, 7, 9), full_attn_layers=(4, 8)),
     [((D,), 1), ((K, K, M, K), 2)]),
], ids=["published-27", "cut-5", "two-periods-9"])
def test_layer_plan_scans_the_period(cfg, plan):
    """The published depth is seven layer bodies, not 27."""
    got = kl.layer_plan(kl.layer_kinds(cfg))
    assert got == plan
    assert sum(len(pattern) * repeats for pattern, repeats in got) == cfg.n_layers


def test_a_layer_in_both_lists_or_in_none_is_refused():
    for lists in (dict(kda_layers=(1, 2, 3, 4, 5)), dict(kda_layers=(1, 2, 5))):
        with pytest.raises(ValueError, match="exactly one"):
            kl.layer_kinds(dataclasses.replace(TINY, **lists))


# ---- (a) the whole model against the plain reference --------------------------

def _reference_sizes(cfg):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "num_hidden_layers": cfg.n_layers,
        "linear_attn_config": {"num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
                               "kda_layers": list(cfg.kda_layers),
                               "full_attn_layers": list(cfg.full_attn_layers)},
        "num_attention_heads": cfg.n_heads, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "num_experts_per_token": cfg.experts_per_token,
        "held_expert_ids": list(cfg.held_experts), "first_k_dense_replace": cfg.first_k_dense,
        "routed_scaling_factor": cfg.routed_scaling_factor}


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=9, kda_layers=(1, 2, 3, 5, 6, 7, 9), full_attn_layers=(4, 8),
                        held_experts=(3, 8, 9, 15)),
    dataclasses.replace(TINY, n_layers=3, kda_layers=(2,), full_attn_layers=(1, 3), first_k_dense=0,
                        remat=False),
], ids=["cut-5", "two-periods-scanned", "no-dense-no-remat"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Loss and every gradient leaf, on seeded weights, with every kind of
    layer; 96 tokens a row are a chunk and a half of the delta rule."""
    from benchmarks.reference.kimi_linear import loss_fn as reference_loss

    params = kl.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 96), 0, cfg.vocab_size)
    loss, grads = kl.make_grad_step(cfg)(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, _reference_sizes(cfg), None)))(params, tokens)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == 37
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape
        if not r.size:  # a group this pattern has no layer of
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


def _parent_mla_attention(h, p, cfg):
    """``_mla_attention`` as it stood in this file's model before the
    function moved to ``models/mla.py`` (PR 34), word for word."""
    from torchft_tpu.models.transformer import _rms_norm
    from torchft_tpu.ops.ring_attention import dense_attention

    b, t, _ = h.shape
    nh, act = cfg.n_heads, cfg.dtype
    nope, rope, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    with jax.named_scope("mla"):
        q = (h @ p["wq"].astype(act)).reshape(b, t, nh, nope + rope)
        kv_a = h @ p["kv_a"].astype(act)
        latent = _rms_norm(kv_a[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
        k_pe = jnp.broadcast_to(kv_a[..., None, rank:], (b, t, nh, rope))
        kv = (latent @ p["kv_b"].astype(act)).reshape(b, t, nh, nope + dv)
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        v = kv[..., nope:]
        o = dense_attention(q, k, v, causal=True)
        return o.reshape(b, t, nh * dv) @ p["wo"].astype(act)


def test_the_lifted_latent_attention_leaves_this_model_bit_for_bit():
    """The latent attention now lives in ``models/mla.py`` and serves two
    families.  On this file's seeded tiny preset the loss and every gradient
    are the parent commit's to the last bit (recorded there, on this CPU
    backend), and the step traces to the program the parent's function
    traces to, operation for operation."""
    import hashlib

    params = kl.init_params(jax.random.PRNGKey(5), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 96), 0, TINY.vocab_size)
    loss, grads = kl.make_grad_step(TINY)(params, tokens)
    assert float(loss).hex() == "0x1.5e285a0000000p+2"
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(grads):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == "855598f6b69608efbda03ddd97f11e0580395dfaeaa1ec2801f80ab5b4a04028"
    sums = {name: float(jnp.sum(g)).hex() for name, g in grads["mla"].items()}
    assert sums == {"attn_norm": "-0x1.2538180000000p-5", "kv_a": "0x1.6663c80000000p-3",
                    "kv_b": "-0x1.fc60780000000p-5", "kv_norm": "0x1.9b534a0000000p-8",
                    "wo": "0x1.d1fd7a0000000p-6", "wq": "-0x1.d267ea0000000p-5"}
    # the same operations in the same order, whatever machine this runs on
    p = jax.tree_util.tree_map(lambda w: w[0], params["mla"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 32))
    assert str(jax.make_jaxpr(lambda h, p: kl._mla_attention(h, p, TINY))(h, p)) == str(
        jax.make_jaxpr(lambda h, p: _parent_mla_attention(h, p, TINY))(h, p))


def test_logits_and_loss_agree():
    params = kl.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, TINY.vocab_size)
    logits = kl.forward(params, tokens, TINY)
    assert logits.shape == (2, 64, TINY.vocab_size) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(float(kl.loss_fn(params, tokens, TINY)), float(want), rtol=1e-5)


def test_norm_epsilon_is_a_field():
    from torchft_tpu.models.transformer import _rms_norm

    x = jnp.full((1, 4), 1e-3)
    assert float(_rms_norm(x, jnp.ones(4))[0, 0]) == pytest.approx(1e-3 / np.sqrt(1e-6 + 1e-6))
    assert float(_rms_norm(x, jnp.ones(4), 1e-5)[0, 0]) == pytest.approx(1e-3 / np.sqrt(1e-6 + 1e-5))
    params = kl.init_params(jax.random.PRNGKey(2), TINY)
    tokens = jnp.zeros((1, 64), jnp.int32)
    a = kl.loss_fn(params, tokens, TINY)
    b = kl.loss_fn(params, tokens, dataclasses.replace(TINY, rms_norm_eps=1e-2))
    assert float(a) != float(b)


# ---- routing stats and their counters ----------------------------------------

def test_routing_stats_over_all_shares_count_every_assignment():
    params = kl.init_params(jax.random.PRNGKey(4), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, TINY.vocab_size)
    n, k = tokens.size, TINY.experts_per_token
    landed = np.zeros(4, np.int64)
    for share in range(4):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(4 * share, 4 * share + 4)))
        stats = kl.make_routing_stats(cfg)(params, tokens)
        assert stats["assignments"].shape == (4, 4) and stats["unrouted"].shape == (4,)
        assert bool((stats["unrouted"] >= 0).all()) and bool((stats["unrouted"] <= n).all())
        # the layers before the first expert layer are the same on every share
        landed[0] += int(stats["assignments"][0].sum())
    assert landed[0] == n * k


def test_routing_stats_feed_the_counters():
    from torchft_tpu.utils import metrics

    cfg = dataclasses.replace(TINY, held_experts=(2, 5, 11, 12))
    params = kl.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
    stats = kl.make_routing_stats(cfg)(params, tokens)

    def read():
        return metrics.parse_text_exposition(metrics.REGISTRY.render())

    before = read()
    kl.record_routing_stats(stats, cfg)
    after = read()

    def value(families, name, **labels):
        key = (name, tuple(sorted(labels.items())))
        samples = {(n, tuple(sorted(l))): v for (n, l), v in families.get(name, {"samples": {}})["samples"].items()}
        return samples.get(key, 0.0)

    # expert layers are layers 2-5 of the model, experts by their published id
    for row, layer in enumerate((2, 3, 4, 5)):
        for slot, expert in enumerate(cfg.held_experts):
            grew = value(after, "torchft_moe_assignments_total", layer=str(layer), expert=str(expert)) - value(
                before, "torchft_moe_assignments_total", layer=str(layer), expert=str(expert))
            assert grew == int(stats["assignments"][row, slot])
        grew = value(after, "torchft_moe_tokens_unrouted_total", layer=str(layer)) - value(
            before, "torchft_moe_tokens_unrouted_total", layer=str(layer))
        assert grew == int(stats["unrouted"][row])


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    """The model's gradient tree at a small size: 37 leaves in four stacked
    groups, last dimensions of 4 (the convolutions), 2 (``a_log``, ``b_proj``),
    24 (``kv_a``), a four-dimensional expert leaf; none a multiple of 128."""
    params = kl.init_params(jax.random.PRNGKey(11), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0, TINY.vocab_size)
    _, grads = kl.make_grad_step(TINY)(params, tokens)
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    grads = _gradient_tree()
    leaves, tree = jax.tree_util.tree_flatten(grads)
    assert {leaf.shape[-1] for leaf in leaves} >= {2, 4, 24} and max(leaf.ndim for leaf in leaves) == 4
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/kimi", f"rank{r}", r, 2), range(2)))
            # rank 0 hands over the device's arrays, rank 1 numpy scaled by 3
            sides = [leaves, [3.0 * np.asarray(leaf) for leaf in leaves]]
            out = list(ex.map(lambda r: pgs[r].allreduce(sides[r], REDUCE_AVG).wait(timeout=60), range(2)))
    finally:
        for pg in pgs:
            pg.shutdown()
        store.shutdown()
    for res in out:
        back = jax.tree_util.tree_unflatten(tree, res)
        assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(grads)
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
        assert np.asarray(got).shape == np.asarray(want).shape
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
