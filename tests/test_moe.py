"""MoE expert-parallel FFN: routing parity, capacity, sharding, grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from torchft_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_ffn,
    moe_ffn_reference,
    moe_param_specs,
)


def _cfg(**kw):
    base = dict(
        d_model=16, d_ff=32, n_experts=4, top_k=2, capacity_factor=4.0,
        dtype=jnp.float32,
    )
    base.update(kw)
    return MoEConfig(**base)


def _setup(cfg, b=2, t=8, seed=0):
    params = init_moe_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t, cfg.d_model))
    return params, x


class TestRouting:
    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_reference_no_drops(self, top_k):
        cfg = _cfg(top_k=top_k)  # capacity 4.0: nothing dropped
        params, x = _setup(cfg)
        y, aux = moe_ffn(x, params, cfg)
        ref = moe_ffn_reference(x, params, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
        assert np.isfinite(float(aux))

    def test_capacity_drops_pass_through_as_zero(self):
        # capacity so small most tokens drop; output shrinks toward zero but
        # stays finite, aux unchanged by drops
        cfg = _cfg(capacity_factor=0.1)
        params, x = _setup(cfg)
        y, aux = moe_ffn(x, params, cfg)
        assert np.isfinite(np.asarray(y)).all()
        full = moe_ffn(x, params, _cfg())[0]
        assert np.abs(np.asarray(y)).sum() < np.abs(np.asarray(full)).sum()

    def test_aux_loss_near_one_for_uniform_router(self):
        cfg = _cfg()
        params, x = _setup(cfg)
        params = dict(params, router=jnp.zeros_like(params["router"]))
        _, aux = moe_ffn(x, params, cfg)
        # uniform probs: E * sum_e f_e * (1/E) = sum_e f_e = 1
        np.testing.assert_allclose(float(aux), 1.0, atol=1e-5)


class TestSharded:
    def test_ep_sharded_matches_unsharded(self):
        # ep-only mesh: inner weight dims stay unsharded
        cfg = _cfg(n_experts=8, fsdp_axis=None, tp_axis=None)
        params, x = _setup(cfg, b=2, t=16)
        ref, _ = moe_ffn(x, params, cfg)

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("ep",))
        specs = moe_param_specs(cfg)
        sharded_params = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(
                p, jax.sharding.NamedSharding(mesh, s)
            ),
            params,
            specs,
        )
        y, _ = jax.jit(lambda xx, pp: moe_ffn(xx, pp, cfg, mesh=mesh))(
            x, sharded_params
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)

    def test_ep_with_fsdp_tp_axes(self):
        cfg = _cfg(n_experts=4)
        params, x = _setup(cfg, b=2, t=16)
        ref, _ = moe_ffn(x, params, cfg)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("ep", "fsdp", "tp"))
        specs = moe_param_specs(cfg)
        sharded_params = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(p, jax.sharding.NamedSharding(mesh, s)),
            params,
            specs,
        )
        y, _ = jax.jit(lambda xx, pp: moe_ffn(xx, pp, cfg, mesh=mesh))(
            x, sharded_params
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


class TestGrads:
    def test_grad_flows_through_router_and_experts(self):
        cfg = _cfg()
        params, x = _setup(cfg)

        def loss(p, xx):
            y, aux = moe_ffn(xx, p, cfg)
            return (y ** 2).mean() + 0.01 * aux

        grads = jax.grad(loss)(params, x)
        for name in ("router", "w_gate", "w_up", "w_down"):
            g = np.asarray(grads[name])
            assert np.isfinite(g).all()
            assert np.abs(g).sum() > 0, f"no gradient through {name}"

    def test_stacked_layers_init(self):
        cfg = _cfg()
        params = init_moe_params(jax.random.PRNGKey(0), cfg, n_layers=3)
        assert params["w_gate"].shape == (3, cfg.n_experts, 16, 32)
        specs = moe_param_specs(cfg, stacked=True)
        assert len(specs["w_gate"]) == 4


class TestTransformerMoE:
    def test_moe_transformer_forward_and_loss(self):
        from torchft_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
            n_layers=2, max_seq_len=32, dtype=jnp.float32, n_experts=4,
        )
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        assert params["blocks"]["w_gate"].shape == (2, 4, 32, 64)
        assert "router" in params["blocks"]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        logits, aux = tfm.forward(params, tokens, cfg, return_aux=True)
        assert logits.shape == (2, 16, 64)
        assert float(aux) > 0
        loss = tfm.loss_fn(params, tokens, cfg)
        assert np.isfinite(float(loss))
        grads = jax.grad(tfm.loss_fn)(params, tokens, cfg)
        g = np.asarray(grads["blocks"]["router"])
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

    def test_moe_transformer_sharded_ep(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchft_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
            n_layers=2, max_seq_len=32, dtype=jnp.float32, n_experts=4,
        )
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        # batch divides dp*fsdp*ep = 4 (ep rides the batch dims)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        ref = tfm.loss_fn(params, tokens, cfg)

        mesh = Mesh(
            np.array(jax.devices()).reshape(2, 1, 2, 1, 2),
            ("dp", "fsdp", "tp", "cp", "ep"),
        )
        sharded = tfm.shard_params(params, mesh, cfg)
        tok_sharded = jax.device_put(
            tokens, NamedSharding(mesh, tfm.batch_spec(cfg))
        )
        loss = jax.jit(
            lambda p, t: tfm.loss_fn(p, t, cfg, mesh=mesh)
        )(sharded, tok_sharded)
        np.testing.assert_allclose(float(loss), float(ref), rtol=2e-5)


# ---------------------------------------------------------------------------
# a chip's share of a sigmoid-routed layer: the shared expert and the
# renormalisation's epsilon are fields whose defaults are the three families'
# ---------------------------------------------------------------------------


class TestHeldMoEFields:
    CFG = dict(d_model=32, d_expert=16, n_routed=16, top_k=4, held=(0, 1, 2, 3), routed_scale=2.5,
               dtype=jnp.float32)

    @staticmethod
    def _digest(*arrays):
        import hashlib

        digest = hashlib.sha256()
        for a in arrays:
            digest.update(np.asarray(a).tobytes())
        return digest.hexdigest()

    def _setup(self, **over):
        from torchft_tpu.models import moe

        cfg = moe.HeldMoEConfig(**{**self.CFG, **over})
        params = moe.init_held_moe_params(jax.random.PRNGKey(3), cfg, 2)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
        return moe, cfg, params, jax.tree_util.tree_map(lambda w: w[0], params), x

    def test_the_defaults_are_the_parents_tree_output_and_gradient_bit_for_bit(self):
        """Recorded on the parent commit (before ``shared`` and ``renorm_eps``
        were fields), on this CPU backend: the tree the initialiser makes, the
        layer's output and stats, every leaf's gradient."""
        moe, cfg, params, p0, x = self._setup()
        assert (cfg.shared, cfg.renorm_eps) == (True, 1e-20)
        assert sorted(params) == ["router", "shared_down", "shared_gate", "shared_up", "w_down", "w_gate", "w_up"]
        assert self._digest(*(params[n] for n in sorted(params))) == (
            "338d32b33d47e84abe205368ddff37cef1823a9bc5c1116bcb4fb6b72333af60")
        y, stats = jax.jit(lambda x, p: moe.held_moe_ffn(x, p, cfg))(x, p0)
        assert int(stats["unrouted"]) == 15
        assert self._digest(y, stats["assignments"]) == (
            "02908dbc940dfaf4e39517c6d0d2e45bf629cbbf71859cc4868798fadf32bfe1")
        g = jax.jit(jax.grad(lambda p: (moe.held_moe_ffn(x, p, cfg)[0] ** 2).sum()))(p0)
        assert self._digest(*(g[n] for n in sorted(g))) == (
            "40e2cb59f172078e7b2c6cd12e6eb1b339133e3e91b9dbfef34ae785d9109d6a")

    def test_without_a_shared_expert_the_tree_has_no_such_leaf_and_the_routed_leaves_are_the_same(self):
        moe, _, with_shared, _, _ = self._setup()
        _, cfg, params, _, _ = self._setup(shared=False)
        assert sorted(params) == ["router", "w_down", "w_gate", "w_up"]
        for name, leaf in params.items():
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(with_shared[name]))

    def test_without_a_shared_expert_the_output_is_the_routed_part_alone(self):
        """``y(shared) - SwiGLU_shared(x) == y(no shared)``; a token with no
        expert here gets exactly zero, and no ``moe.shared`` scope is opened."""
        moe, cfg, _, p0, x = self._setup()
        flat = x.reshape(-1, 32)
        shared = ((jax.nn.silu(flat @ p0["shared_gate"]) * (flat @ p0["shared_up"]))
                  @ p0["shared_down"]).reshape(x.shape)
        whole, _ = moe.held_moe_ffn(x, p0, cfg)
        bare_cfg = moe.HeldMoEConfig(**{**self.CFG, "shared": False})
        bare_p = {n: w for n, w in p0.items() if not n.startswith("shared")}
        routed, stats = jax.jit(lambda x, p: moe.held_moe_ffn(x, p, bare_cfg))(x, bare_p)
        np.testing.assert_allclose(np.asarray(routed), np.asarray(whole - shared), rtol=1e-5, atol=1e-6)
        chosen, _ = moe.route_sigmoid(flat, p0["router"], bare_cfg)
        nowhere = np.asarray((chosen >= 4).all(axis=-1))
        assert int(nowhere.sum()) == int(stats["unrouted"]) == 15
        assert np.all(np.asarray(routed).reshape(-1, 32)[nowhere] == 0.0)
        assert np.all(np.abs(np.asarray(routed).reshape(-1, 32)[~nowhere]).max(axis=-1) > 0)
        text = jax.jit(lambda x, p: moe.held_moe_ffn(x, p, bare_cfg)).lower(x, bare_p).as_text(debug_info=True)
        assert "moe.experts" in text and "moe.shared" not in text
        assert "moe.shared" in jax.jit(lambda x, p: moe.held_moe_ffn(x, p, cfg)).lower(x, p0).as_text(debug_info=True)

    @pytest.mark.parametrize("eps", [1e-20, 1e-6, 0.5])
    def test_the_epsilon_reaches_the_weights(self, eps):
        """``w = s[chosen] / (sum s[chosen] + eps) * scale``: the weights' sum
        a token is ``scale * S / (S + eps)``."""
        moe, _, _, p0, x = self._setup()
        cfg = moe.HeldMoEConfig(**{**self.CFG, "renorm_eps": eps})
        flat = x.reshape(-1, 32)
        chosen, weights = moe.route_sigmoid(flat, p0["router"], cfg)
        scores = jax.nn.sigmoid(flat @ p0["router"])
        total = jnp.take_along_axis(scores, chosen, axis=-1).sum(-1)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), np.asarray(2.5 * total / (total + eps)), rtol=1e-5)
        if eps == 0.5:
            same, base = moe.route_sigmoid(flat, p0["router"], moe.HeldMoEConfig(**self.CFG))
            np.testing.assert_array_equal(np.asarray(same), np.asarray(chosen))
            assert float(jnp.abs(base - weights).max()) > 0.05, "the choice is the same, the weights are not"


# ---------------------------------------------------------------------------
# the router's score is a field whose default is the four families'; a share
# may hold more experts than a token chooses
# ---------------------------------------------------------------------------


class TestHeldMoEScore:
    CFG, _setup = TestHeldMoEFields.CFG, TestHeldMoEFields._setup
    WIDE = dict(d_model=32, d_expert=16, n_routed=32, top_k=8, held=tuple(range(16)), shared=False,
                renorm_eps=0.0, score="softmax", dtype=jnp.float32)

    @staticmethod
    def _text_digest(jaxpr):
        import hashlib

        return hashlib.sha256(str(jaxpr).encode()).hexdigest()

    def test_the_default_score_is_the_parents_program_jaxpr_for_jaxpr(self):
        """Recorded on the parent commit (before ``score`` was a field and the
        placement a function of its own): the layer's jaxpr forward, with a
        ``router_bias``, and its gradient's.  The output's and the gradient's
        recorded digests are ``TestHeldMoEFields``'s."""
        moe, cfg, _, p0, x = self._setup()
        assert cfg.score == "sigmoid"
        assert self._text_digest(jax.make_jaxpr(lambda x, p: moe.held_moe_ffn(x, p, cfg))(x, p0)) == (
            "eacbb3746136df42c39bc4fd4a7832e8de3d3bfc299a1d956f26004c130d52cd")
        bias = jnp.zeros((16,))
        assert self._text_digest(jax.make_jaxpr(
            lambda x, p: moe.held_moe_ffn(x, p, cfg, router_bias=bias))(x, p0)) == (
            "47319df017d6bcd50f3f29781a084a55f0a05dd362ab42235912504b4b854385")
        assert self._text_digest(jax.make_jaxpr(jax.grad(
            lambda p: (moe.held_moe_ffn(x, p, cfg)[0] ** 2).sum()))(p0)) == (
            "327534181c757e03da54e1de459de6ea817306e87ba6eb7953146824b9782525")

    def test_softmax_weights_sum_to_one_and_are_a_softmax_over_the_chosen_logits(self):
        """``p[chosen] / sum p[chosen]`` with ``p`` over all 16: the
        normaliser over all cancels, what is left is a softmax over the chosen
        logits alone; the choice is the largest logits."""
        moe, _, _, p0, x = self._setup()
        cfg = moe.HeldMoEConfig(**{**self.CFG, "routed_scale": 1.0, "renorm_eps": 0.0, "score": "softmax"})
        flat = x.reshape(-1, 32)
        chosen, weights = moe.route_softmax(flat, p0["router"], cfg)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
        logits = flat @ p0["router"]
        _, by_logit = jax.lax.top_k(logits, 4)
        np.testing.assert_array_equal(np.asarray(chosen), np.asarray(by_logit))
        alone = jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
        np.testing.assert_allclose(np.asarray(weights), np.asarray(alone), rtol=2e-5)
        # and not the sigmoid's: the same router gives other weights
        _, other = moe.route_sigmoid(flat, p0["router"], cfg)
        assert float(jnp.abs(other - weights).max()) > 0.01
        scaled = moe.route_softmax(flat, p0["router"], moe.HeldMoEConfig(**{**self.CFG, "score": "softmax"}))[1]
        np.testing.assert_allclose(np.asarray(scaled.sum(-1)), 2.5, rtol=1e-6)

    def test_the_layer_picks_its_router_by_the_field(self):
        moe, _, _, p0, x = self._setup()
        sig = moe.HeldMoEConfig(**self.CFG)
        soft = moe.HeldMoEConfig(**{**self.CFG, "score": "softmax"})
        y_sig, _ = moe.held_moe_ffn(x, p0, sig)
        y_soft, _ = moe.held_moe_ffn(x, p0, soft)
        assert float(jnp.abs(y_sig - y_soft).max()) > 1e-3
        text = jax.jit(lambda x, p: moe.held_moe_ffn(x, p, soft)).lower(x, p0).as_text(debug_info=True)
        for scope in ("moe.route", "moe.route.score", "moe.route.place", "moe.experts"):
            assert scope in text, scope
        with pytest.raises(ValueError, match="router_bias"):
            moe.held_moe_ffn(x, p0, soft, router_bias=jnp.zeros((16,)))
        with pytest.raises(ValueError, match="score"):
            moe.held_moe_ffn(x, p0, moe.HeldMoEConfig(**{**self.CFG, "score": "topk"}))

    @pytest.mark.parametrize("slack,pool", [(8.0, 384), (1.0, 192), (0.5, 96), (0.26, 56)])
    def test_the_pool_where_more_experts_are_held_than_a_token_chooses(self, slack, pool):
        """16 of 32 held, 8 a token, 48 tokens: the mean load is 192 rows;
        the cap is every assignment of the batch (48 x 8 = 384), where with
        ``held <= top_k`` it was ``N held``."""
        from torchft_tpu.models import moe

        cfg = moe.HeldMoEConfig(**{**self.WIDE, "slack": slack})
        assert moe.pool_rows(cfg, 48) == pool
        assert moe.pool_rows(moe.HeldMoEConfig(**{**self.WIDE, "held": (0, 1, 2, 3), "slack": 100.0}), 48) == 48 * 4

    def test_sixteen_held_of_eight_chosen_fill_the_pools_rows_in_expert_order(self):
        """Every landed assignment has one row; an expert's rows follow the
        previous expert's, by token then choice; a token lands up to eight
        times; the rest of the pool names the row of zeros at weight 0."""
        from torchft_tpu.models import moe

        cfg = moe.HeldMoEConfig(**{**self.WIDE, "slack": 2.0})
        params = moe.init_held_moe_params(jax.random.PRNGKey(3), cfg, 1)
        flat = jax.random.normal(jax.random.PRNGKey(4), (48, 32))
        chosen, weights = moe.route_softmax(flat, params["router"][0], cfg)
        local, assignments, unrouted, rows_token, rows_weight = map(
            np.asarray, moe.place_assignments(chosen, weights, cfg))
        chosen, weights = np.asarray(chosen), np.asarray(weights)
        assert rows_token.shape == rows_weight.shape == (384,)
        want_token, want_weight = [], []
        for e in range(16):
            for token in range(48):
                for choice in range(8):
                    if chosen[token, choice] == e:
                        want_token.append(token)
                        want_weight.append(weights[token, choice])
        landed = len(want_token)
        assert landed == int(assignments.sum()) == int((local >= 0).sum()) > 48, "a token lands more than once"
        assert [int((chosen == e).sum()) for e in range(16)] == assignments.tolist()
        np.testing.assert_array_equal(rows_token[:landed], want_token)
        np.testing.assert_array_equal(rows_weight[:landed], np.asarray(want_weight, np.float32))
        assert np.all(rows_token[landed:] == 48) and np.all(rows_weight[landed:] == 0.0)
        assert int(unrouted) == int((chosen >= 16).all(-1).sum())
        assert max(np.bincount(rows_token[:landed])) > 1

    @pytest.mark.parametrize("slack", [2.0, 1.0, 0.5, 0.05])
    def test_a_batch_over_the_pool_takes_the_masked_path_to_the_same_output(self, slack, monkeypatch):
        """The gathered path while what landed fits ``pool_rows``, the masked
        one from the first assignment over: which ran is the ``cond``'s
        predicate; both give the uncut arithmetic."""
        from torchft_tpu.models import moe

        cfg = moe.HeldMoEConfig(**{**self.WIDE, "slack": slack})
        p0 = jax.tree_util.tree_map(lambda w: w[0], moe.init_held_moe_params(jax.random.PRNGKey(3), cfg, 1))
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
        taken, real = [], jax.lax.cond
        monkeypatch.setattr(jax.lax, "cond", lambda pred, *rest: (taken.append(bool(pred)), real(pred, *rest))[1])
        y, stats = moe.held_moe_ffn(x, p0, cfg)
        landed = int(stats["assignments"].sum())
        assert taken == [landed <= moe.pool_rows(cfg, 48)]
        if slack in (2.0, 0.05):  # the cap holds every assignment; 16 rows hold next to none
            assert taken[0] == (slack == 2.0)
        flat = x.reshape(-1, 32)
        chosen, weights = moe.route_softmax(flat, p0["router"], cfg)
        want = jnp.zeros_like(flat)
        for e in range(16):
            glu = (jax.nn.silu(flat @ p0["w_gate"][e]) * (flat @ p0["w_up"][e])) @ p0["w_down"][e]
            want = want + jnp.where(chosen == e, weights, 0.0).sum(-1, keepdims=True) * glu
        np.testing.assert_allclose(np.asarray(y).reshape(-1, 32), np.asarray(want), rtol=2e-4, atol=2e-5)
