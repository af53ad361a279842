"""What the causal flash kernels do with a call's tiles is a pure function of
its shapes (``ops/flash_attention.py`` ``tile_kinds``), and the gauge
``torchft_flash_tiles{kind}`` reads a grad step's sum of it off the traced
program (``models/transformer.py`` ``_grad_step``), as
``torchft_remat_kept_bytes`` is read.  Nothing runs here: the steps are only
traced."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.models import afmoe, transformer
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.utils import metrics


def _tiles(kinds):
    return kinds["under"], kinds["diagonal"], kinds["above"], kinds["general"]


@pytest.mark.parametrize("shapes,tiles", [
    # tiles of 1024: the flagship cell's 2 x 2, the long rows' 8 x 8
    ((2048, 2048, 64, 64), (1, 2, 1, 0)),
    ((8192, 8192, 192, 128), (28, 8, 28, 0)),
    ((4096, 4096, 192, 128), (6, 4, 6, 0)),
    ((1024, 1024, 64, 64), (0, 1, 0, 0)),
    # a window of 2048 on tiles of 1024, a band of three: the diagonal tile and
    # the older edge are cut, the tile between them is not; three steps of
    # the first two query tiles fall before the sequence's start
    ((8192, 8192, 128, 128, 2048), (7, 14, 3, 0)),
    # a window that ends inside a tile has two tiles on its older edge
    ((1024, 1024, 64, 64, 300), (7, 8 + 6 + 5, 3 + 2 + 1, 0)),
    # offsets (the ring composition) and tiles that are not square are
    # decided at run time
    ((2048, 2048, 64, 64, None, True), (0, 0, 0, 4)),
    ((2048, 1024, 64, 64), (1, 1, 0, 0)),  # fewer keys than queries, square tiles still
    ((2048, 512, 64, 64), (0, 0, 0, 2)),
])
def test_a_heads_tiles_by_kind(shapes, tiles):
    kinds = fa.tile_kinds(*shapes)
    assert set(kinds) == set(fa.TILE_KINDS) and _tiles(kinds) == tiles


@pytest.mark.parametrize("shapes", [
    (2048, 2048, 64, 64), (8192, 8192, 192, 128), (8192, 8192, 128, 128, 2048)])
def test_the_sub_blocks_are_each_kernels_own_cut(shapes):
    """Computed and skipped sub-blocks add up to every cut tile's ``n x n`` in
    each of the three kernels; a diagonal tile computes ``n (n + 1) / 2``."""
    kinds = fa.tile_kinds(*shapes)
    blk = fa._tiles(shapes[0], shapes[1], max(shapes[2:4]), *shapes[4:5] or (None,))[0]
    ns = [blk // fa._sub_block(kernel, blk, *shapes[2:4]) for kernel in ("fwd", "bwd_kv", "bwd_q")]
    assert kinds["sub_computed"] + kinds["sub_skipped"] == kinds["diagonal"] * sum(n * n for n in ns)
    assert kinds["sub_computed"] == kinds["diagonal"] * sum(n * (n + 1) // 2 for n in ns)


def test_the_pieces_of_a_diagonal_tile():
    """Blocks of rows against the keys up to their own last one, masked by
    how far the block's first query lies after the stretch's first key; by
    key, blocks of keys against the queries from their own first one on."""
    assert fa._pieces(1024, 512, 0, None) == (
        (slice(0, 512), slice(0, 512), 0), (slice(512, 1024), slice(0, 1024), 512))
    assert fa._pieces(1024, 512, 0, None, by_key=True) == (
        (slice(0, 1024), slice(0, 512), 0), (slice(512, 1024), slice(512, 1024), 0))
    assert fa._pieces(1024, 1024, 0, None) == ((slice(0, 1024), slice(0, 1024), 0),)
    # a tile wholly under the diagonal is one unmasked piece, one above it none
    assert fa._pieces(1024, 256, 1024, None) == ((slice(0, 256), slice(0, 1024), None),) + tuple(
        (slice(r, r + 256), slice(0, 1024), None) for r in (256, 512, 768))
    assert fa._pieces(1024, 256, -1024, None) == ()
    # the older edge of a window of two tiles: the keys after the block's rows
    edge = fa._pieces(1024, 512, 2048, 2048)
    assert edge == ((slice(0, 512), slice(0, 1024), 2048), (slice(512, 1024), slice(512, 1024), 2048))
    # only there can a row be empty: the forward keeps its guard for it
    assert [fa._can_be_empty(p, 2048) for p in edge] == [False, True]
    assert not any(fa._can_be_empty(p, None) for p in fa._pieces(1024, 256, 0, None))


def _dense(t, heads=2, layers=3):
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=64 * heads, n_heads=heads, n_kv_heads=1, d_ff=64, n_layers=layers,
        max_seq_len=t, dtype=jnp.bfloat16, attn_impl="flash")
    return transformer, cfg


def _gauge_after_tracing(module, cfg, batch, t):
    for kind in fa.TILE_KINDS:
        metrics.FLASH_TILES.labels(kind=kind).set(-1)
    params = jax.eval_shape(lambda: module.init_params(jax.random.PRNGKey(0), cfg))
    module.make_grad_step(cfg).trace(params, jax.ShapeDtypeStruct((batch, t), jnp.int32))
    return {kind: metrics.FLASH_TILES.labels(kind=kind).get() for kind in fa.TILE_KINDS}


@pytest.mark.parametrize("t,a_head", [(2048, (1, 2, 1, 0)), (8192, (28, 8, 28, 0))])
@pytest.mark.parametrize("change", [{}, {"remat_policy": "dots"}, {"remat": False}],
                         ids=["full", "dots", "no-remat"])
def test_the_gauge_sums_a_grad_steps_calls(t, a_head, change):
    """Batch 2, two heads, three scanned layers: twelve heads' tiles, whatever
    the checkpoint keeps."""
    module, cfg = _dense(t)
    got = _gauge_after_tracing(module, dataclasses.replace(cfg, **change), 2, t)
    assert _tiles(got) == tuple(2 * 2 * 3 * n for n in a_head)
    per_head = fa.tile_kinds(t, t, 64, 64)
    assert got == {kind: 12 * n for kind, n in per_head.items()}


def test_the_gauge_reads_zero_without_a_flash_call():
    module, cfg = _dense(256)
    got = _gauge_after_tracing(module, dataclasses.replace(cfg, attn_impl="dense"), 2, 256)
    assert got == dict.fromkeys(fa.TILE_KINDS, 0)


def test_the_gauge_adds_window_and_global_layers():
    """Five window layers and one global one (``afmoe``): each call's own
    tiles, a window's band beside the causal grid."""
    s, f = "sliding_attention", "full_attention"
    cfg = afmoe.AfmoeConfig(
        vocab_size=128, d_model=32, n_layers=6, layer_types=(s, s, s, f), num_dense_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, sliding_window=256, d_ff=64, d_expert=16, n_routed_experts=16,
        experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32, attn_impl="flash")
    got = _gauge_after_tracing(afmoe, cfg, 2, 2048)
    window, causal = fa.tile_kinds(2048, 2048, 8, 8, 256), fa.tile_kinds(2048, 2048, 8, 8)
    assert _tiles(window) == (15, 16 + 14, 1 + 2, 0) and _tiles(causal) == (1, 2, 1, 0)
    assert got == {kind: 2 * 4 * (5 * window[kind] + causal[kind]) for kind in fa.TILE_KINDS}
