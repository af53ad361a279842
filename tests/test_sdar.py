"""The SDAR model (``models/sdar.py``) and what it brings with it: a training
step that runs a row twice, noised beside clean, under the block-diffusion
mask; noise that is a pure function of the row and a seed (the program's draw
against the reference's own lines, and across a heal's serialisation); a loss
over the masked positions, weighted by the row's level, without a shift; the
shared expert layer on ``2T`` positions with the mask token's crowd in layer
0.  The whole step against the benchmark's plain reference is
``tests/test_sdar_reference.py``."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import moe, sdar

TINY = sdar.SDARConfig(
    vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8, d_expert=16,
    n_routed_experts=16, experts_per_token=4, held_experts=tuple(range(8)), noise_seed=5,
    dtype=jnp.float32, attn_impl="dense")


def _tokens(cfg, rows=2, t=64, seed=6):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, t), 0, cfg.vocab_size)


def _reference_sizes(cfg):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.experts_per_token, "held_expert_ids": list(cfg.held_experts),
        "num_hidden_layers": cfg.n_layers, "block_length": cfg.block_length,
        "mask_token_id": cfg.mask_id(), "noise_seed": cfg.noise_seed, "t_eps": cfg.t_eps}


# ---- the tree -------------------------------------------------------------------

def test_the_tree_counts_the_published_parameters():
    """48 layers of attention 18,878,720 + router 262,144 + 128 experts of
    4,718,592, an untied vocabulary of 151,936: the published 30B; the cut
    the benchmark runs (4 layers, 16 experts, an eighth of the rows) 456 M."""
    def count(cfg):
        shapes = jax.eval_shape(lambda k: sdar.init_params(k, cfg), jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))

    whole = sdar.SDARConfig(held_experts=tuple(range(128)))
    assert count(whole) == 48 * (18_878_720 + 262_144 + 128 * 4_718_592) + 2 * 151_936 * 2048 + 2048
    assert count(whole) == 30_532_122_624
    assert count(sdar.SDARConfig(n_layers=4, vocab_size=18_992)) == 456_346_624
    params = sdar.init_params(jax.random.PRNGKey(0), TINY)
    assert sorted(params) == ["attn", "embed", "final_norm", "head", "moe"]
    assert params["attn"]["wq"].shape == (3, 32, 32) and params["moe"]["w_down"].shape == (3, 8, 16, 32)
    assert "shared_gate" not in params["moe"] and params["head"].shape == (32, 128)


# ---- the noise ------------------------------------------------------------------

def test_the_noise_is_a_function_of_the_row_and_the_seed_alone():
    """A row draws the same level and the same mask wherever it stands in a
    batch and whatever stands beside it; another seed or another row draws
    another; a masked position holds the mask token, the others their own."""
    tokens = _tokens(TINY, rows=3)
    noised, masked, p = sdar.corrupt(tokens, TINY)
    assert noised.shape == masked.shape == tokens.shape and p.shape == (3,) and masked.dtype == jnp.bool_
    assert np.all((np.asarray(p) >= TINY.t_eps) & (np.asarray(p) <= 1.0))
    assert TINY.mask_id() == 127 and np.all(np.asarray(noised)[np.asarray(masked)] == 127)
    assert np.array_equal(np.asarray(noised)[~np.asarray(masked)], np.asarray(tokens)[~np.asarray(masked)])
    again, _, p_again = sdar.corrupt(tokens[::-1], TINY)
    assert np.array_equal(np.asarray(again)[::-1], np.asarray(noised)) and np.array_equal(p_again[::-1], p)
    alone = jax.jit(lambda t: sdar.corrupt(t, TINY))(tokens[1:2])
    assert np.array_equal(np.asarray(alone[1][0]), np.asarray(masked[1])) and float(alone[2][0]) == float(p[1])
    other_seed = sdar.corrupt(tokens, dataclasses.replace(TINY, noise_seed=6))
    assert not np.array_equal(np.asarray(other_seed[2]), np.asarray(p))
    moved = tokens.at[0, 7].set((tokens[0, 7] + 1) % 128)       # one token of row 0
    _, masked_moved, p_moved = sdar.corrupt(moved, TINY)
    assert float(p_moved[0]) != float(p[0]) and np.array_equal(np.asarray(p_moved[1:]), np.asarray(p[1:]))
    swapped = tokens.at[0, :2].set(tokens[0, :2][::-1])            # the same tokens in another order
    assert tokens[0, 0] != tokens[0, 1] and float(sdar.corrupt(swapped, TINY)[2][0]) != float(p[0])


def test_the_levels_are_uniform_and_the_masks_follow_them():
    rows = jax.random.randint(jax.random.PRNGKey(1), (256, 128), 0, 128)
    _, masked, p = jax.jit(lambda t: sdar.corrupt(t, TINY))(rows)
    p, share = np.asarray(p), np.asarray(masked).mean(axis=1)
    assert 0.42 < p.mean() < 0.58 and p.min() < 0.05 and p.max() > 0.95
    assert np.abs(share - p).max() < 0.2 and abs(share.mean() - p.mean()) < 0.02


def test_the_programs_draw_is_the_references():
    """Each side writes the configuration's rule out on its own; the two
    draws are equal bit for bit, a row at a time or a batch at once."""
    from benchmarks.reference.sdar import row_noise

    tokens = _tokens(TINY, rows=4, t=96)
    _, masked, p = sdar.corrupt(tokens, TINY)
    sizes = _reference_sizes(TINY)
    for r in range(4):
        ref_masked, ref_p = row_noise(tokens[r], sizes)
        assert np.array_equal(np.asarray(ref_masked), np.asarray(masked[r])) and float(ref_p) == float(p[r])


def test_a_healed_replica_replays_the_step_bit_for_bit():
    """The state a heal carries is ``params`` and the optimizer's alone; sent
    through the heal's fragments and put together again it gives the same
    noise, the same loss and the same gradients to the last bit: there is no
    generator state to carry."""
    import optax

    from torchft_tpu.checkpointing import fragments as frags

    params = sdar.init_params(jax.random.PRNGKey(2), TINY)
    opt_state = optax.adamw(1e-3).init(params)
    tokens = _tokens(TINY)
    step = sdar.make_grad_step(TINY)
    loss, grads = step(params, tokens)
    state = {"params": params, "opt_state": opt_state, "step": 3}
    header, parts = frags.iter_heal_fragments(state, 6)
    leaves = {}
    for _name, raw, _digest in parts:
        leaves.update(frags.decode_fragment(raw))
    healed = frags.assemble(header, leaves)
    assert jax.tree_util.tree_structure(healed) == jax.tree_util.tree_structure(state)
    healed_params = jax.tree_util.tree_map(jnp.asarray, healed["params"])
    loss2, grads2 = step(healed_params, tokens)
    assert float(loss2) == float(loss)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---- the mask and the positions -----------------------------------------------

def test_the_plane_is_the_three_lines():
    t, block = 16, 4
    seen = sdar.diffusion_mask(t, block)
    assert seen.shape == (32, 32) and seen.sum() == t * t + t * block
    assert not seen[t:, :t].any(), "a clean query sees no noised key"
    assert np.array_equal(seen[:t, :t], np.kron(np.eye(4, dtype=bool), np.ones((4, 4), bool)))
    assert np.array_equal(seen[t:, t:], np.kron(np.tril(np.ones((4, 4), bool)), np.ones((4, 4), bool)))
    assert np.array_equal(seen[:t, t:], np.kron(np.tril(np.ones((4, 4), bool), -1), np.ones((4, 4), bool)))
    assert not seen[:block, t:].any(), "the first block's noised queries see no clean key"


def _embedded(params, both, cfg):
    from torchft_tpu.models.transformer import _embed

    return _embed(params, both, cfg, sharded=False)


def test_both_copies_of_a_token_turn_by_the_same_angle():
    """With no position masked the noised copy is the clean copy, and a
    noised query then sees what the clean query of its token sees (its own
    block's keys, equal on both copies, and the clean blocks before it): the
    two halves of a layer's output are equal, which they are only if position
    ``i`` and ``T + i`` turn by the same rotary angle.  And a clean query
    sees no noised key: the clean half does not move when the noised does."""
    cfg = dataclasses.replace(TINY, remat=False)
    params = sdar.init_params(jax.random.PRNGKey(3), cfg)
    first = [{name: leaf[0] for name, leaf in params[g].items()} for g in sdar.GROUPS]
    tokens = _tokens(cfg, rows=1)
    layer = sdar._make_layer(sdar._KIND, cfg)
    x, _ = layer(_embedded(params, jnp.concatenate([tokens, tokens], axis=1), cfg), *first)
    np.testing.assert_allclose(np.asarray(x[0, :64]), np.asarray(x[0, 64:]), rtol=1e-5, atol=1e-5)
    noised, masked, _ = sdar.corrupt(tokens, cfg)
    y, _ = layer(_embedded(params, jnp.concatenate([noised, tokens], axis=1), cfg), *first)
    np.testing.assert_allclose(np.asarray(y[0, 64:]), np.asarray(x[0, 64:]), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y[0, :64] - x[0, :64]).max()) > 1e-2 and bool(masked.any())


def test_the_last_layer_runs_its_clean_copy_for_keys_and_values_alone():
    """The last layer gives back the noised copy's ``T`` positions, equal to
    the noised half of a whole layer on the same input, and its experts see
    ``T`` positions a row; the walk cuts it from the scan's run."""
    from torchft_tpu.models.kimi_linear import layer_plan

    cfg = dataclasses.replace(TINY, remat=False)
    params = sdar.init_params(jax.random.PRNGKey(3), cfg)
    first = [{name: leaf[0] for name, leaf in params[g].items()} for g in sdar.GROUPS]
    tokens = _tokens(cfg)
    noised, _, _ = sdar.corrupt(tokens, cfg)
    x = _embedded(params, jnp.concatenate([noised, tokens], axis=1), cfg)
    whole, stats = sdar._make_layer(sdar._KIND, cfg)(x, *first)
    last, last_stats = sdar._make_layer(sdar._LAST, cfg)(x, *first, {})
    assert last.shape == (2, 64, 32) and whole.shape == (2, 128, 32)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, :64]), rtol=1e-5, atol=1e-6)
    assert int(stats["assignments"].sum()) > int(last_stats["assignments"].sum()) > 0
    assert layer_plan([sdar._KIND] * 3 + [sdar._LAST]) == [((sdar._KIND,), 3), ((sdar._LAST,), 1)]
    hidden, every = sdar.forward_hidden(params, jnp.concatenate([noised, tokens], axis=1), cfg)
    assert hidden.shape == (2, 64, 32) and every["assignments"].shape == (3, 8)
    with pytest.raises(ValueError, match="whole number of blocks"):
        sdar.forward_hidden(params, jnp.zeros((1, 2 * 62), jnp.int32), cfg)


# ---- logits, loss, dtypes --------------------------------------------------------

def test_logits_and_loss_agree():
    """``forward`` gives the noised copy's logits; the loss is their weighted
    cross-entropy on the clean tokens at the same positions."""
    params = sdar.init_params(jax.random.PRNGKey(2), TINY)
    tokens = _tokens(TINY)
    logits = sdar.forward(params, tokens, TINY)
    assert logits.shape == (2, 64, 128) and logits.dtype == jnp.float32
    _, masked, p = sdar.corrupt(tokens, TINY)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tokens[..., None], axis=-1)[..., 0]
    want = float((nll * masked / p[:, None]).sum() / tokens.size)
    assert float(sdar.loss_fn(params, tokens, TINY)) == pytest.approx(want, rel=1e-5)


def test_bfloat16_compute_keeps_float32_parameters_and_gradients():
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    params = sdar.init_params(jax.random.PRNGKey(2), cfg)
    loss, grads = sdar.make_grad_step(cfg)(params, _tokens(cfg))
    assert np.isfinite(float(loss)) and loss.dtype == jnp.float32
    assert all(g.dtype == jnp.float32 and np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


# ---- the share ties to the model ---------------------------------------------

def _uncut_layer(x, p, top_k):
    """The reference's expert layer with every expert held: each expert on
    every position, its weights as a mask; no shared expert."""
    flat = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(flat @ p["router"], axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    weight = picked / picked.sum(-1, keepdims=True)
    out = jnp.zeros_like(flat)
    for e in range(p["w_gate"].shape[0]):
        glu = (jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e])) @ p["w_down"][e]
        out = out + jnp.where(chosen == e, weight, 0.0).sum(-1, keepdims=True) * glu
    return out.reshape(x.shape)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The deployment's cut: 128 experts scored, 8 a position, 16 held by
    each of 8 chips.  The eight shares' outputs, simply added (nothing is
    shared, so nothing is counted once), are the uncut layer; every
    assignment lands on one share; a position none of whose experts lives on
    a share gets exactly zero from it (a third of them, if uniform)."""
    d, f, n_routed, top_k = 32, 12, 128, 8
    model = dataclasses.replace(TINY, d_model=d, d_expert=f, n_routed_experts=n_routed, experts_per_token=top_k)
    whole = dataclasses.replace(model, held_experts=tuple(range(n_routed))).moe()
    full = {name: leaf[0] for name, leaf in moe.init_held_moe_params(jax.random.PRNGKey(8), whole, 1).items()}
    assert sorted(full) == ["router", "w_down", "w_gate", "w_up"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 96, d))
    _, chosen = jax.lax.top_k(jax.nn.softmax(x.reshape(-1, d) @ full["router"], axis=-1), top_k)
    total, landed, unrouted = 0.0, 0, []
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        cfg = dataclasses.replace(model, held_experts=held).moe()
        assert (cfg.n_routed, cfg.top_k, cfg.held, cfg.shared, cfg.score) == (128, 8, held, False, "softmax")
        mine = dict(full, **{name: full[name][np.asarray(held)] for name in ("w_gate", "w_up", "w_down")})
        y, stats = jax.jit(lambda x, p, c=cfg: moe.held_moe_ffn(x, p, c))(x, mine)
        nowhere = np.asarray(((chosen < held[0]) | (chosen > held[-1])).all(-1))
        assert int(stats["unrouted"]) == int(nowhere.sum())
        assert np.all(np.asarray(y).reshape(-1, d)[nowhere] == 0.0), "no expert here, nothing from the FFN"
        total = total + y
        landed += int(stats["assignments"].sum())
        unrouted.append(int(stats["unrouted"]))
    assert landed == 2 * 96 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(_uncut_layer(x, full, top_k)), rtol=2e-4, atol=2e-5)
    assert math.comb(112, 8) / math.comb(128, 8) == pytest.approx(0.3326, abs=1e-3)
    assert 0.15 < np.mean(unrouted) / 192 < 0.5


# ---- routing over 2T positions, the counters ---------------------------------

def _read(name, **labels):
    from torchft_tpu.utils import metrics

    samples = metrics.parse_text_exposition(metrics.REGISTRY.render()).get(name, {"samples": {}})["samples"]
    return {(n, tuple(sorted(l))): v for (n, l), v in samples.items()}.get(
        (name, tuple(sorted(labels.items()))), 0.0)


def test_the_mask_tokens_crowd_goes_one_way_in_layer_0():
    """Layer 0's input is the embedding: every masked position of the noised
    copy is the same vector there, so the router sends them all to the same
    ``experts_per_token`` experts.  Both shares' first layers hold every one
    of the ``2T`` positions' assignments."""
    params = sdar.init_params(jax.random.PRNGKey(4), TINY)
    tokens = _tokens(TINY, rows=4)
    noised, masked, _ = sdar.corrupt(tokens, TINY)
    crowd = int(masked.sum())
    assert crowd > 32
    landed, crowded = 0, 0
    for share in range(2):
        cfg = dataclasses.replace(TINY, held_experts=tuple(range(8 * share, 8 * share + 8)))
        stats = sdar.make_routing_stats(cfg)(params, tokens)
        assert stats["assignments"].shape == (3, 8) and stats["unrouted"].shape == (3,)
        assert float(stats["masked_share"]) == pytest.approx(crowd / tokens.size) and stats["p"].shape == (4,)
        landed += int(stats["assignments"][0].sum())
        crowded += int((np.asarray(stats["assignments"][0]) >= crowd).sum())
    assert landed == 2 * tokens.size * TINY.experts_per_token, "2T positions a row go through the router"
    assert crowded >= TINY.experts_per_token, "the crowd's experts hold at least the crowd"


def test_routing_stats_feed_the_counters_and_the_noise_gauges():
    cfg = dataclasses.replace(TINY, held_experts=(0, 1, 2, 3, 4, 5, 11, 12))
    params = sdar.init_params(jax.random.PRNGKey(4), cfg)
    tokens = _tokens(cfg, seed=9)
    stats = sdar.make_routing_stats(cfg)(params, tokens)
    layers = (0, 1, 2)
    keys = [(row, layer, slot, e) for row, layer in enumerate(layers) for slot, e in enumerate(cfg.held_experts)]
    before = [_read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) for _, layer, _, e in keys]
    lost = [_read("torchft_moe_tokens_unrouted_total", layer=str(layer)) for layer in layers]
    sdar.record_routing_stats(stats, cfg)
    for (row, layer, slot, e), was in zip(keys, before):
        assert _read("torchft_moe_assignments_total", layer=str(layer), expert=str(e)) - was == int(
            stats["assignments"][row, slot])
    for row, (layer, was) in enumerate(zip(layers, lost)):
        assert _read("torchft_moe_tokens_unrouted_total", layer=str(layer)) - was == int(stats["unrouted"][row])
    _, masked, p = sdar.corrupt(tokens, cfg)
    assert _read("torchft_diffusion_masked_share") == pytest.approx(float(masked.mean()))
    for row in range(2):
        assert _read("torchft_diffusion_noise_level", row=str(row)) == pytest.approx(float(p[row]))


def test_the_step_keeps_both_flash_calls_results_and_opens_the_models_scopes():
    """Full remat through ``transformer._remat``: a layer keeps the clean
    copy's and the noised copy's forward results (``o`` and ``lse`` of ``T``
    positions each: the gauge reads their bytes; the last layer has the
    noised copy's call alone) and counts the calls' tiles; the lowered
    program names the scopes the per-layer metrics read."""
    cfg = dataclasses.replace(TINY, n_layers=2, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64,
                              attn_impl="flash", dtype=jnp.bfloat16)
    params = sdar.init_params(jax.random.PRNGKey(5), cfg)
    tokens = _tokens(cfg, t=128)
    step = sdar.make_grad_step(cfg)
    text = step.lower(params, tokens).as_text(debug_info=True)
    assert _read("torchft_remat_kept_bytes") == 3 * (2 * 128 * 2 * 64 * 2 + 2 * 2 * 128 * 4), \
        "a call's B T H Dv x 2 B + B H T x 4 B; two calls in the first layer, one in the last"
    assert _read("torchft_flash_tiles", kind="diagonal") == 3 * 4, "a tile a head, 4 heads, three calls"
    for scope in ("embed", "sdar.corrupt", "attn.proj", "attn.diffusion", "attn.rope", "moe.route",
                  "moe.route.score", "moe.route.place", "moe.experts", "moe.gathered", "moe.masked", "head",
                  "sdar.loss"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert "moe.shared" not in text


# ---- the fault-tolerance layer on the new tree --------------------------------

def _gradient_tree():
    params = sdar.init_params(jax.random.PRNGKey(11), TINY)
    _, grads = sdar.make_grad_step(TINY)(params, _tokens(TINY, seed=12))
    return grads


def test_the_ring_averages_the_new_tree():
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import StoreServer
    from torchft_tpu.parallel.process_group import REDUCE_AVG, ProcessGroupTCP

    grads = _gradient_tree()
    leaves, tree = jax.tree_util.tree_flatten(grads)
    assert len(leaves) == 15 and max(leaf.ndim for leaf in leaves) == 4
    store = StoreServer()
    pgs = [ProcessGroupTCP(timeout=30.0) for _ in range(2)]
    try:
        with ThreadPoolExecutor(2) as ex:
            list(ex.map(lambda r: pgs[r].configure(f"{store.address()}/sdar", f"rank{r}", r, 2), range(2)))
            sides = [leaves, [3.0 * np.asarray(leaf) for leaf in leaves]]
            out = list(ex.map(lambda r: pgs[r].allreduce(sides[r], REDUCE_AVG).wait(timeout=60), range(2)))
    finally:
        for pg in pgs:
            pg.shutdown()
        store.shutdown()
    for res in out:
        for got, leaf in zip(res, leaves):
            assert got.shape == leaf.shape and got.dtype == leaf.dtype
            np.testing.assert_allclose(np.asarray(got), 2.0 * np.asarray(leaf), rtol=1e-6, atol=1e-12)


def test_the_model_trains_through_the_manager_and_the_optimizer_wrapper():
    """A lone group, as ``benchmarks/harness/loop.py`` drives every family:
    the same ``Manager``, ``DistributedDataParallel`` and ``Optimizer``, the
    same ``grad_step(params, tokens)``; the loss falls over a few steps on one
    batch (whose noise is the same draw every step)."""
    import optax

    import torchft_tpu as ft
    from torchft_tpu.coordination import LighthouseServer

    lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=10_000)
    tx = optax.adamw(3e-3)
    params = sdar.init_params(jax.random.PRNGKey(1), TINY)
    state = {"params": params, "opt_state": tx.init(params)}
    manager = ft.Manager(
        pg=ft.ProcessGroupTCP(timeout=30.0), min_replica_size=1,
        load_state_dict=state.update, state_dict=lambda: dict(state),
        replica_id="sdar_0", lighthouse_addr=lighthouse.address(), group_rank=0, group_world_size=1,
        use_async_quorum=True, timeout=30.0, quorum_timeout=30.0, init_sync=False)
    try:
        ddp, optimizer = ft.DistributedDataParallel(manager), ft.Optimizer(manager, tx)
        step, tokens = sdar.make_grad_step(TINY), _tokens(TINY)
        losses = []
        for _ in range(4):
            optimizer.begin_step()
            loss, grads = step(state["params"], tokens)
            avg = ddp.allreduce_gradients(grads).wait(timeout=30.0)
            assert manager.should_commit()
            state["params"], state["opt_state"] = optimizer.update(state["params"], avg, state["opt_state"])
            losses.append(float(loss))
    finally:
        manager.shutdown()
        lighthouse.shutdown()
    assert manager.current_step() == 4 and losses[-1] < losses[0]
