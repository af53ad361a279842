"""Flash attention (Pallas, interpret mode on CPU) vs dense reference.

The kernel must match dense_attention in both directions of AD — it is
the bench flagship's attention (attn_impl='flash') so a numerics drift
here is a silent model-quality bug.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops.flash_attention import flash_attention
from torchft_tpu.ops.ring_attention import dense_attention


def _qkv(b=2, t=256, h=4, hkv=2, d=64, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, t, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, hkv, d), dtype)
    return q, k, v


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        ref = dense_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5
        )

    def test_multiple_block_sizes(self):
        # 128 / 256 / 512 / 1024 block selection paths (1024 engages at
        # head_dim <= 256 when it divides T — the flagship tile)
        for t in (128, 384, 512, 1024):
            q, k, v = _qkv(t=t, seed=t)
            ref = dense_attention(q, k, v)
            out = flash_attention(q, k, v)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5
            )

    def test_block_ladder_head_dim_gate(self):
        from torchft_tpu.ops.flash_attention import _block_size

        assert _block_size(1024, 256) == 1024
        assert _block_size(1024, 512) == 512  # wide heads keep 512 tiles
        assert _block_size(512, 256) == 512
        assert _block_size(384, 64) == 128

    def test_fully_masked_rows_yield_zero_not_mean_of_v(self):
        # A chunk whose queries all PRECEDE every key (causal ring chunk
        # with q_off < k_off) has zero live keys per row: the kernel must
        # emit O == 0 and lse ~ -inf for such rows, not exp(-inf - -inf)=1
        # weights (a garbage mean of V).
        from torchft_tpu.ops.flash_attention import _fwd, _to3

        q, k, v = _qkv(t=128)
        scale = 1.0 / np.sqrt(q.shape[-1])
        h = q.shape[2]
        ke = jnp.repeat(k, h // k.shape[2], axis=2)
        ve = jnp.repeat(v, h // v.shape[2], axis=2)
        # keys start INSIDE the first tile (k_off=64): rows 0..63 are fully
        # masked within a tile the block-level `needed` gate keeps live, so
        # this exercises the p-masking line (an out-of-tile offset like 4096
        # would be skipped by the gate and pass even without the fix)
        offs = jnp.array([0, 64], jnp.int32)
        o, lse = _fwd(_to3(q), _to3(ke), _to3(ve), scale, True, offs)
        o, lse = np.asarray(o), np.asarray(lse)
        np.testing.assert_array_equal(o[:, :64], 0.0)
        assert np.all(lse[:, :64] < -1e20)
        # live rows are untouched by the masking
        assert np.all(np.isfinite(o[:, 64:])) and np.any(o[:, 64:] != 0.0)

    def test_rejects_unaligned_seq(self):
        q, k, v = _qkv(t=100)
        with pytest.raises(ValueError, match="128"):
            flash_attention(q, k, v)

    def test_gqa_head_broadcast(self):
        q, k, v = _qkv(h=8, hkv=2)
        ref = dense_attention(q, k, v)
        out = flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-5
        )


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv()

        def make_loss(fn):
            def loss(q, k, v):
                out = fn(q, k, v, causal=causal)
                # non-uniform cotangent exercises dq/dk/dv paths properly
                w = jnp.arange(out.size, dtype=out.dtype).reshape(out.shape)
                return (out * w).mean()

            return jax.grad(loss, argnums=(0, 1, 2))

        g_ref = make_loss(dense_attention)(q, k, v)
        g_out = make_loss(flash_attention)(q, k, v)
        for name, a, b in zip("qkv", g_out, g_ref):
            scale = float(np.abs(np.asarray(b)).max()) + 1e-12
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale,
                atol=1e-5, err_msg=f"d{name}",
            )


class TestFlashInTransformer:
    def test_forward_matches_dense_impl(self):
        from torchft_tpu.models import transformer as tfm

        base = dict(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            n_layers=2, max_seq_len=128, dtype=jnp.float32,
        )
        params = tfm.init_params(
            jax.random.PRNGKey(0), tfm.TransformerConfig(**base)
        )
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
        ref = tfm.forward(
            params, tokens, tfm.TransformerConfig(attn_impl="dense", **base)
        )
        out = tfm.forward(
            params, tokens, tfm.TransformerConfig(attn_impl="flash", **base)
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4
        )

    def test_train_step_grads_finite(self):
        import optax

        from torchft_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            n_layers=2, max_seq_len=128, dtype=jnp.float32,
            attn_impl="flash",
        )
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        optimizer = optax.adamw(1e-3)
        step = tfm.make_train_step(cfg, optimizer, donate=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
        params2, _, loss = step(params, optimizer.init(params), tokens)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(params2):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_rejects_manual_context(self):
        # flash does not nest in the pipeline's manual shard_map context
        from torchft_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            n_layers=2, max_seq_len=128, attn_impl="flash",
            dtype=jnp.float32,
        )
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        block = tfm._make_block(cfg, "manual")
        x = jnp.zeros((2, 128, 64), jnp.float32)
        layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        with pytest.raises(ValueError, match="manual shard_map"):
            block(x, layer0, jnp.arange(128))


class TestFlashOnMesh:
    def test_batch_and_head_sharded_matches_dense(self):
        # flash on a dp x tp mesh: batch and heads shard, each device runs
        # the kernel on its full-sequence shard
        from jax.sharding import Mesh, NamedSharding

        from torchft_tpu.models import transformer as tfm

        base = dict(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
            n_layers=2, max_seq_len=128, dtype=jnp.float32,
        )
        cfg = tfm.TransformerConfig(attn_impl="flash", **base)
        cfg_dense = tfm.TransformerConfig(attn_impl="dense", **base)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 64)
        ref = tfm.forward(params, tokens, cfg_dense)

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
        sharded = tfm.shard_params(params, mesh, cfg)
        tok_sh = jax.device_put(
            tokens, NamedSharding(mesh, tfm.batch_spec(cfg, mesh))
        )
        out = jax.jit(lambda p, t: tfm.forward(p, t, cfg, mesh))(sharded, tok_sh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4
        )

    def test_rejects_cp_mesh(self):
        from jax.sharding import Mesh

        from torchft_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            n_layers=2, max_seq_len=128, attn_impl="flash", dtype=jnp.float32,
        )
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("cp",))
        block = tfm._make_block(cfg, mesh)
        x = jnp.zeros((2, 128, 64), jnp.float32)
        layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        with pytest.raises(ValueError, match="sequence unsharded"):
            block(x, layer0, jnp.arange(128))


class TestFlashBf16:
    def test_bf16_matches_dense_within_tolerance(self):
        # the production dtype: matmuls in bf16 with f32 accumulation in
        # BOTH impls — agreement bound is bf16 resolution, not exactness
        q, k, v = _qkv(dtype=jnp.bfloat16, seed=3)
        ref = np.asarray(dense_attention(q, k, v), np.float32)
        out = np.asarray(flash_attention(q, k, v), np.float32)
        scale = np.abs(ref).max() + 1e-9
        assert np.abs(out - ref).max() / scale < 3e-2


class TestFlashUnequalWidths:
    """Queries and keys wider than values (latent attention: 192 against
    128): forward and every gradient against dense attention."""

    @staticmethod
    def _qkv(d, dv, h=2, hkv=2, t=256, seed=5):
        key = jax.random.PRNGKey(seed)
        q = jax.random.normal(jax.random.fold_in(key, 0), (2, t, h, d), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, t, hkv, d), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, t, hkv, dv), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("d,dv,hkv,causal", [
        (192, 128, 2, True), (192, 128, 1, True), (192, 128, 2, False),
        (64, 128, 2, True), (128, 64, 2, True),
    ])
    def test_forward_and_grads_match_dense(self, d, dv, hkv, causal):
        q, k, v = self._qkv(d, dv, hkv=hkv)
        out = flash_attention(q, k, v, causal=causal)
        assert out.shape == q.shape[:3] + (dv,)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense_attention(q, k, v, causal=causal)),
            atol=2e-5, rtol=1e-5)

        def grads(fn):
            def loss(q, k, v):
                o = fn(q, k, v, causal=causal)
                return (o * jnp.arange(o.size, dtype=o.dtype).reshape(o.shape)).mean()

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        for name, a, b in zip("qkv", grads(flash_attention), grads(dense_attention)):
            assert a.shape == b.shape
            top = float(np.abs(np.asarray(b)).max()) + 1e-12
            np.testing.assert_allclose(np.asarray(a) / top, np.asarray(b) / top,
                                       atol=1e-5, err_msg=f"d{name}")


def _masked_softmax(q, k, v, window):
    """The window's definition, written out: query ``i`` sees key ``j`` iff
    ``0 <= i - j < window``; grouped queries read key-value head ``h // rep``."""
    t, d = q.shape[1], q.shape[-1]
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


class TestFlashWindow:
    """A sliding window: the three kernels walk the band's tiles only, against
    a plain masked softmax.  The sequences are several tiles long (a window's
    tile is at most half of it, 128 at the least)."""

    # (T, window): shorter than a tile; not a multiple of the tile (a band of
    # three 128-tiles); bands of three and four 256-tiles with one wholly
    # inside, which runs unmasked; one key short of causal; equal to T and
    # longer (plain causal attention)
    CASES = [(512, 100), (512, 200), (1024, 512), (1024, 640), (384, 383), (384, 384), (384, 1000)]

    @pytest.mark.parametrize("t,window", CASES)
    def test_forward_matches_a_masked_softmax(self, t, window):
        q, k, v = _qkv(t=t, h=4, hkv=2, seed=t + window)
        out = flash_attention(q, k, v, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_masked_softmax(q, k, v, window)), atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("t,window", CASES)
    def test_three_gradients_match_a_masked_softmax(self, t, window):
        q, k, v = _qkv(t=t, h=4, hkv=1, seed=t + window)  # grouped four to one
        weight = jax.random.normal(jax.random.PRNGKey(9), q.shape)

        def grads(fn):
            return jax.grad(lambda q, k, v: (fn(q, k, v) * weight).sum(), argnums=(0, 1, 2))(q, k, v)

        got = grads(lambda q, k, v: flash_attention(q, k, v, window=window))
        want = grads(lambda q, k, v: _masked_softmax(q, k, v, window))
        for name, a, b in zip("qkv", got, want):
            top = float(np.abs(np.asarray(b)).max()) + 1e-12
            np.testing.assert_allclose(np.asarray(a) / top, np.asarray(b) / top,
                                       atol=1e-5, err_msg=f"d{name}")

    def test_the_band_is_the_tiles_a_query_tile_can_see(self):
        from torchft_tpu.ops.flash_attention import _tiles

        # square tiles of half the window, of those that divide the sequence;
        # the diagonal tile and those back to the tile of key i - window + 1
        assert _tiles(8192, 8192, 128, 2048) == (1024, 1024, 3)
        assert _tiles(8192, 8192, 128, 1024) == (512, 512, 3)
        assert _tiles(512, 512, 64, 1) == (128, 128, 1)
        assert _tiles(512, 512, 64, 129) == (128, 128, 2) and _tiles(512, 512, 64, 130) == (128, 128, 3)
        assert _tiles(384, 384, 128, 2048) == (128, 128, 3)  # never more than there are
        assert _tiles(2048, 1024, 64, None) == (1024, 1024, None)
        with pytest.raises(ValueError, match="128"):
            _tiles(100, 100, 64, 64)

    @pytest.mark.parametrize("window,names", [
        (None, {"_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel"}),
        (256, {"_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel"}),  # no shorter than the sequence
        (100, {"_fwd_window_kernel", "_bwd_kv_window_kernel", "_bwd_q_window_kernel"}),
    ])
    def test_windowed_calls_carry_names_of_their_own(self, window, names):
        """A trace tells window from global calls; the window-less call keeps
        the names every existing cell's roofline share finds its kernels by."""
        q, k, v = _qkv(t=256)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, window=window).sum(), argnums=(0, 1, 2)))(q, k, v))
        import re

        assert set(re.findall(r"name=(_\w+kernel)", text)) == names

    def test_the_window_less_call_is_what_it_was(self):
        """``window=None`` and a window no shorter than the sequence run the
        causal kernels: bit for bit the call without the argument."""
        q, k, v = _qkv(t=256, seed=11)

        def both(**kw):
            out, grads = jax.value_and_grad(
                lambda q, k, v: (flash_attention(q, k, v, **kw) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
            return [out, *grads]

        plain = both()
        for kw in ({"window": None}, {"window": 256}, {"causal": True, "window": 9999}):
            for a, b in zip(both(**kw), plain):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_a_window_needs_causal_self_attention(self):
        q, k, v = _qkv(t=128)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, causal=False, window=64)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, window=0)
        with pytest.raises(ValueError, match="window"):
            dense_attention(q, k, v, causal=False, window=64)

    def test_dense_attention_takes_the_same_window(self):
        q, k, v = _qkv(t=128, seed=4)
        np.testing.assert_allclose(
            np.asarray(dense_attention(q, k, v, window=40)), np.asarray(_masked_softmax(q, k, v, 40)),
            atol=2e-5, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(dense_attention(q, k, v, window=128)), np.asarray(dense_attention(q, k, v)))


class TestFlashTilesByPlace:
    """A causal call without offsets does a tile's work by its place: tiles
    under the diagonal unmasked and unguarded, the tiles the mask cuts (the
    diagonal one, a window's older edge) in blocks that leave out the
    sub-blocks with no live pair, nothing above the diagonal.  Grids with
    tiles of all three kinds, against dense attention."""

    @staticmethod
    def _tiles_of(monkeypatch, tile, sub):
        """Tiles of at most ``tile`` (the test's ``at_most``) cut into blocks
        of ``sub``, so that a short sequence has a grid of several tiles."""
        from torchft_tpu.ops import flash_attention as fa

        block_size = fa._block_size
        monkeypatch.setattr(
            fa, "_block_size", lambda t, d, at_most=1024: block_size(t, d, min(at_most, tile)))
        if sub is not None:
            monkeypatch.setattr(fa, "_sub_block", lambda kernel, blk, d, dv: min(sub, blk))

    @staticmethod
    def _qkv(t, d, dv, h, hkv, seed=7):
        key = jax.random.PRNGKey(seed)
        q = jax.random.normal(jax.random.fold_in(key, 0), (1, t, h, d), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, t, hkv, d), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, t, hkv, dv), jnp.float32)
        return q, k, v

    @staticmethod
    def _check(q, k, v, reference, **kw):
        weight = jax.random.normal(jax.random.PRNGKey(9), q.shape[:3] + v.shape[3:])

        def both(fn):
            out, grads = jax.value_and_grad(
                lambda q, k, v: (fn(q, k, v) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
            return [fn(q, k, v), *grads]

        got, want = both(lambda q, k, v: flash_attention(q, k, v, **kw)), both(reference)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            top = float(np.abs(np.asarray(b)).max()) + 1e-12
            np.testing.assert_allclose(np.asarray(a) / top, np.asarray(b) / top,
                                       atol=1e-5, err_msg=name)

    # heads of 64 with grouped keys and values, 192 against 128; four tiles of
    # 128 a side: six under the diagonal, four on it, six above
    @pytest.mark.parametrize("sub", [32, 64, 128, None], ids=lambda s: f"blocks-{s}")
    @pytest.mark.parametrize("d,dv,h,hkv", [(64, 64, 4, 2), (192, 128, 2, 2)])
    def test_forward_and_three_gradients_match_dense(self, monkeypatch, d, dv, h, hkv, sub):
        self._tiles_of(monkeypatch, 128, sub)
        q, k, v = self._qkv(512, d, dv, h, hkv)
        self._check(q, k, v, dense_attention)

    def test_one_head_at_2048_on_tiles_of_1024(self):
        """The flagship cell's grid, 2 x 2, as the rule cuts it."""
        from torchft_tpu.ops.flash_attention import tile_kinds

        kinds = tile_kinds(2048, 2048, 64, 64)
        assert (kinds["under"], kinds["diagonal"], kinds["above"]) == (1, 2, 1)
        q, k, v = self._qkv(2048, 64, 64, 1, 1)
        self._check(q, k, v, dense_attention)

    # a window of two tiles (one tile on the older edge, cut like the
    # diagonal), one that ends inside a tile (two tiles on the edge), one
    # shorter than a tile (the diagonal tile holds both edges)
    @pytest.mark.parametrize("window", [256, 200, 100])
    @pytest.mark.parametrize("sub", [32, 128])
    def test_a_windows_edges_are_cut_the_same_way(self, monkeypatch, window, sub):
        self._tiles_of(monkeypatch, 128, sub)
        q, k, v = self._qkv(768, 32, 32, 2, 1)
        self._check(q, k, v, lambda q, k, v: _masked_softmax(q, k, v, window), window=window)

    @pytest.mark.parametrize("d,exact", [(64, True), (256, True), (16, True), (128, False), (192, False)])
    def test_the_scale_is_exact_at_heads_of_a_power_of_four(self, d, exact):
        from torchft_tpu.ops.flash_attention import _exact_scale

        assert _exact_scale(1.0 / np.sqrt(d)) == exact

    @pytest.mark.parametrize("sub", [32, 128])
    def test_folding_the_scale_into_the_queries_changes_no_bit(self, monkeypatch, sub):
        """Heads of 64: ``scale`` is 2^-3, so the kernels multiply the query
        rows and the accumulators in place of the pairs; the output and the
        three gradients are the bits of the same kernels with the scale left
        on the pairs."""
        from torchft_tpu.ops import flash_attention as fa

        self._tiles_of(monkeypatch, 128, sub)
        q, k, v = self._qkv(512, 64, 64, 4, 2)

        def both():
            out, grads = jax.value_and_grad(
                lambda q, k, v: (flash_attention(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
            return [flash_attention(q, k, v), out, *grads]

        assert fa._exact_scale(1.0 / np.sqrt(64))
        folded = both()
        monkeypatch.setattr(fa, "_exact_scale", lambda scale: False)
        on_the_pairs = both()
        for a, b in zip(folded, on_the_pairs):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    @pytest.mark.parametrize("k_off", [64, 128, 4096])
    def test_a_chunk_before_every_key_is_zeros_and_finite_gradients(self, k_off):
        """With offsets a row can have no live key (the ring composition):
        that path keeps the empty-row guard.  Rows before the first key read
        0 with ``lse`` ~ -inf, and the backward gives them and the keys they
        never saw gradients of 0, none of them NaN."""
        from torchft_tpu.ops.flash_attention import _bwd, _fwd, _to3

        q, k, v = _qkv(t=128, hkv=4)
        scale = 1.0 / np.sqrt(q.shape[-1])
        offs = jnp.array([0, k_off], jnp.int32)
        o, lse = _fwd(_to3(q), _to3(k), _to3(v), scale, True, offs)
        dead = min(k_off, 128)
        np.testing.assert_array_equal(np.asarray(o)[:, :dead], 0.0)
        assert np.all(np.asarray(lse)[:, :dead] < -1e20)
        do = jnp.ones_like(o)
        dq, dk, dv = _bwd(_to3(q), _to3(k), _to3(v), o, lse, do, scale, True, offs)
        for g in (dq, dk, dv):
            assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_array_equal(np.asarray(dq)[:, :dead], 0.0)
        assert (k_off >= 128) == (not np.asarray(dk).any())


class TestFlashKeptResults:
    """The forward rule names the kernel's two results (``FLASH_OUT_NAME``,
    ``FLASH_LSE_NAME``): a ``jax.checkpoint`` that saves those names runs the
    forward kernel once where one that keeps nothing runs it twice, to the
    same bits; without such a checkpoint a name is the identity."""

    # causal at heads of 64, windowed, values narrower than keys (192 against
    # 128), not causal, grouped heads of 16
    CASES = {
        "causal-64": (dict(d=64), {}),
        "window": (dict(d=64, t=512), {"window": 100}),
        "values-128-keys-192": (dict(d=192, dv=128), {}),
        "not-causal": (dict(d=64), {"causal": False}),
        "values-16": (dict(d=24, dv=16, h=2, hkv=1), {}),
    }

    @staticmethod
    def _qkv(d, dv=None, t=256, h=4, hkv=2, seed=3):
        key = jax.random.PRNGKey(seed)
        q = jax.random.normal(jax.random.fold_in(key, 0), (2, t, h, d), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, t, hkv, d), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, t, hkv, dv or d), jnp.float32)
        return q, k, v

    @staticmethod
    def _value_and_grads(remat, kw):
        def loss(q, k, v):
            o = remat(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)
            return (o * jnp.arange(o.size, dtype=o.dtype).reshape(o.shape)).mean()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    @staticmethod
    def _keeping(fn):
        from torchft_tpu.ops.flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

        return jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT_NAME, FLASH_LSE_NAME))

    @pytest.mark.parametrize("case", CASES)
    def test_a_checkpoint_that_saves_the_names_runs_the_forward_once(self, case):
        import re

        shapes, kw = self.CASES[case]
        q, k, v = self._qkv(**shapes)
        forward = "_fwd_window_kernel" if "window" in kw else "_fwd_kernel"

        def calls(remat):
            text = str(jax.make_jaxpr(self._value_and_grads(remat, kw))(q, k, v))
            return sorted(re.findall(r"name=(_\w+kernel)", text))

        kept, nothing = calls(self._keeping), calls(jax.checkpoint)
        assert kept.count(forward) == 1 and nothing.count(forward) == 2
        assert [n for n in kept if n != forward] == [n for n in nothing if n != forward]
        assert len(kept) == 3

    @pytest.mark.parametrize("case", CASES)
    def test_kept_and_recomputed_are_the_same_bits(self, case):
        shapes, kw = self.CASES[case]
        q, k, v = self._qkv(**shapes)
        plain = self._value_and_grads(lambda fn: fn, kw)(q, k, v)
        for remat in (self._keeping, jax.checkpoint):
            got = self._value_and_grads(remat, kw)(q, k, v)
            for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(plain)):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    @pytest.mark.parametrize("case", CASES)
    def test_the_names_tag_the_output_in_the_models_rows_and_the_logsumexp(self, case):
        from torchft_tpu.ops.flash_attention import FLASH_CALL_NAME, FLASH_LSE_NAME, FLASH_OUT_NAME

        shapes, kw = self.CASES[case]
        q, k, v = self._qkv(**shapes)
        b, t, h, _ = q.shape
        named = {}
        # the call's shapes, a third name on the logsumexp that no policy saves
        call = f"{FLASH_CALL_NAME}:{b * h}:{t}:{t}:{q.shape[-1]}:{v.shape[-1]}:{kw.get('window', 0)}"

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "name":
                    named[eqn.params["name"]] = eqn.outvars[0].aval
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(self._value_and_grads(lambda fn: fn, kw))(q, k, v).jaxpr)
        assert set(named) == {FLASH_OUT_NAME, FLASH_LSE_NAME, call}
        assert named[FLASH_OUT_NAME].shape == (b, t, h * v.shape[-1])
        assert named[FLASH_LSE_NAME].shape == (b * h, t) and named[FLASH_LSE_NAME].dtype == jnp.float32
        # the primal call, which no gradient is taken of, names nothing
        named.clear()
        walk(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v).jaxpr)
        assert named == {}

    def test_the_ring_composition_names_nothing(self):
        """``ring_flash_local`` calls the kernel itself and combines chunks'
        results: there is no single ``o`` of a layer to keep."""
        from jax.sharding import Mesh, PartitionSpec as P

        from torchft_tpu.ops.flash_attention import ring_flash_local

        q, k, v = _qkv(t=256)
        mesh = Mesh(np.array(jax.devices()[:2]), ("cp",))
        spec = P(None, "cp", None, None)
        fn = jax.shard_map(
            lambda q, k, v: ring_flash_local(q, k, v, "cp", True), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
        text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: fn(q, k, v).sum(), argnums=(0, 1, 2)))(q, k, v))
        assert "flash_attn_" not in text and "_fwd_kernel" in text
