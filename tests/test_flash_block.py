"""The flash kernels under a mask at block granularity
(``ops/flash_attention.py``: ``flash_attention(..., block=, strict=)``), the
block-diffusion composition on them (``flash_block_diffusion``: a row run
twice, noised beside clean, three live parts of a ``[2T, 2T]`` plane that is
never formed, two partial softmaxes merged on one chip) and the helper that
merges partial results, the ring's and this one's.  Every comparison is with
dense attention under an explicit boolean mask written from the equations;
the kernels run in interpret mode, so shapes stay at one or two heads."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import flash_attention as fa

BLOCK = 4


def _plane(t, block):
    """``[2T, 2T]`` booleans, query down, key along: the three lines of the
    block-diffusion mask (BD3-LM's M_BD, M_OBC, M_BC)."""
    seen = np.zeros((2 * t, 2 * t), bool)
    for i in range(2 * t):
        for j in range(2 * t):
            noised_i, noised_j = i < t, j < t
            block_i, block_j = (i % t) // block, (j % t) // block
            seen[i, j] = ((noised_i and noised_j and block_i == block_j)
                          or (noised_i and not noised_j and block_i > block_j)
                          or (not noised_i and not noised_j and block_i >= block_j))
    return seen


def _dense(q, k, v, seen):
    """Softmax over the keys ``seen`` leaves; a query that sees none gets 0."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    probs = jnp.where(seen.any(-1)[None, None, :, None], probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _qkv(t, d, h, hkv, seed=3):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, t, hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, t, hkv, d), jnp.float32)
    return q, k, v


def _both(fn, q, k, v):
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    return jax.value_and_grad(lambda q, k, v: (fn(q, k, v) * weight).sum(), argnums=(0, 1, 2))(q, k, v)


def _agree(got, want):
    (out, grads), (ref, ref_grads) = got, want
    assert np.isfinite(float(out))
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-5, atol=2e-4)
    for g, r in zip(grads, ref_grads):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-5)


def _small_tiles(monkeypatch, tile=128, sub=32):
    """Tiles of ``tile`` cut into blocks of ``sub``: a short row then has a
    grid of several tiles of all three kinds (tests/test_flash_attention.py)."""
    block_size = fa._block_size
    monkeypatch.setattr(fa, "_block_size", lambda t, d, at_most=1024: block_size(t, d, min(at_most, tile)))
    monkeypatch.setattr(fa, "_sub_block", lambda kernel, blk, d, dv: min(sub, blk))


# ---- the mask at block granularity ----------------------------------------------

@pytest.mark.parametrize("strict", [False, True], ids=["block-causal", "strictly"])
@pytest.mark.parametrize("tiles", ["one-tile", "a-grid-of-tiles"])
def test_a_block_mask_is_dense_attention_under_the_blocks_mask(monkeypatch, strict, tiles):
    """``i // block >= j // block`` and its strict form, forward and the three
    gradients, grouped heads; under the strict form the rows of block 0 see
    no key: zeros forward, finite (zero) gradients."""
    if tiles == "a-grid-of-tiles":
        _small_tiles(monkeypatch)
    t = 256 if tiles == "one-tile" else 384
    q, k, v = _qkv(t, 32, 2, 1)
    blocks = np.arange(t) // BLOCK
    seen = blocks[:, None] > blocks[None, :] if strict else blocks[:, None] >= blocks[None, :]
    got = _both(lambda q, k, v: fa.flash_attention(q, k, v, block=BLOCK, strict=strict), q, k, v)
    _agree(got, _both(lambda q, k, v: _dense(q, k, v, jnp.asarray(seen)), q, k, v))
    if strict:
        out = fa.flash_attention(q, k, v, block=BLOCK, strict=True)
        assert np.all(np.asarray(out[:, :BLOCK]) == 0.0) and np.all(np.asarray(got[1][0][:, :BLOCK]) == 0.0)
        assert np.abs(np.asarray(out[:, BLOCK:])).min(axis=-1).max() > 0


def test_a_block_of_one_is_the_causal_mask():
    q, k, v = _qkv(256, 32, 1, 1)
    np.testing.assert_allclose(np.asarray(fa.flash_attention(q, k, v, block=1)),
                               np.asarray(fa.flash_attention(q, k, v)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kw,match", [
    (dict(block=4, window=64), "block mask"), (dict(block=4, causal=False), "block mask"),
    (dict(block=3), "block mask"), (dict(block=0), "block mask"), (dict(strict=True), "strict")])
def test_a_block_mask_the_kernels_cannot_cut_is_refused(kw, match):
    q, k, v = _qkv(256, 32, 1, 1)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention(q, k, v, **kw)


def _digest(fn, *args):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("shape,window,want", [
    ((2, 256, 2, 2, 64, 64), None, "16d78d00cd5523bb"),
    ((1, 4096, 4, 1, 128, 128), None, "9102ee7eef9f2f4b"),
    ((1, 4096, 2, 2, 192, 128), None, "f34ebec26626f2d2"),
    ((1, 8192, 2, 1, 128, 128), 1024, "2d393e555a23002a"),
], ids=["64", "128-grouped", "192-128", "window-1024"])
def test_without_a_block_the_traced_program_is_the_parents(monkeypatch, shape, window, want):
    """``block=None`` lowers to the program the kernels had before they knew
    of blocks: the digest of ``value_and_grad``'s jaxpr (kernel bodies
    included, compiled not interpreted) recorded on the parent commit."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    b, t, h, hkv, d, dv = shape
    q, k = jnp.zeros((b, t, h, d), jnp.bfloat16), jnp.zeros((b, t, hkv, d), jnp.bfloat16)
    v = jnp.zeros((b, t, hkv, dv), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    assert _digest(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, k, v) == want


def test_the_calls_under_a_block_mask_have_names_of_their_own():
    """As the windowed calls: a trace tells the clean copy's calls, the noised
    copy's and a causal model's apart."""
    q, k, v = _qkv(256, 32, 1, 1)

    def names(fn, *args):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: fn(*a).sum(), argnums=(0, 1, 2)))(*args))
        return set(re.findall(r"name=(_\w+_kernel)", text))

    assert names(fa.flash_attention, q, k, v) == {"_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel"}
    assert names(lambda q, k, v: fa.flash_attention(q, k, v, block=4), q, k, v) == {
        "_fwd_block_kernel", "_bwd_kv_block_kernel", "_bwd_q_block_kernel"}
    assert names(lambda q, k, v: fa.flash_attention(q, k, v, block=4, strict=True), q, k, v) == {
        "_fwd_block_strict_kernel", "_bwd_kv_block_strict_kernel", "_bwd_q_block_strict_kernel"}
    both = jnp.concatenate([q, q], axis=1), jnp.concatenate([k, k], axis=1), jnp.concatenate([v, v], axis=1)
    assert names(lambda q, k, v: fa.flash_block_diffusion(q, k, v, 4), *both) == {
        "_fwd_block_kernel", "_bwd_kv_block_kernel", "_bwd_q_block_kernel",
        "_fwd_block_strict_kernel", "_bwd_kv_block_strict_kernel", "_bwd_q_block_strict_kernel"}


def test_only_a_strict_masks_first_block_can_be_empty():
    whole, first, later = (slice(0, 128), slice(0, 128), 0), (slice(0, 64), slice(0, 64), 0), \
        (slice(64, 128), slice(0, 128), 64)
    assert not fa._can_be_empty(whole, None, (4, False))
    assert fa._can_be_empty(whole, None, (4, True)) and fa._can_be_empty(first, None, (4, True))
    assert not fa._can_be_empty(later, None, (4, True))
    assert not fa._can_be_empty((slice(0, 128), slice(0, 128), None), None, (4, True))


# ---- the composition --------------------------------------------------------------

@pytest.mark.parametrize("t,h,hkv,tiles", [(256, 2, 1, False), (384, 1, 1, True)],
                         ids=["one-tile-grouped", "a-grid-of-tiles"])
def test_the_composition_is_dense_attention_under_the_three_part_mask(monkeypatch, t, h, hkv, tiles):
    """Forward and the three gradients over ``2T`` positions against the
    explicit ``[2T, 2T]`` plane, block 0's noised rows (no clean key: their
    own block alone) and a ``T`` of several tiles included."""
    if tiles:
        _small_tiles(monkeypatch)
    q, k, v = _qkv(2 * t, 32, h, hkv)
    seen = _plane(t, BLOCK)
    assert seen[:BLOCK, t:].sum() == 0 and seen.sum() == t * t + t * BLOCK, "a quarter of the plane and 4 T more"
    got = _both(lambda q, k, v: fa.flash_block_diffusion(q, k, v, BLOCK), q, k, v)
    _agree(got, _both(lambda q, k, v: _dense(q, k, v, jnp.asarray(seen)), q, k, v))


def test_the_noised_copys_queries_alone_are_the_compositions_first_half():
    """A last layer asks for the noised copy's rows only: ``q`` of ``T``
    positions against ``k``, ``v`` of ``2T`` gives the first half of the whole
    composition, forward and gradients, and the clean copy's call is not
    made."""
    t = 256
    q, k, v = _qkv(2 * t, 32, 2, 1)
    weight = jax.random.normal(jax.random.PRNGKey(9), q[:, :t].shape)

    def half(fn):
        return jax.value_and_grad(lambda q, k, v: (fn(q, k, v) * weight).sum(), argnums=(0, 1, 2))(q, k, v)

    got = half(lambda q, k, v: fa.flash_block_diffusion(q[:, :t], k, v, BLOCK))
    want = half(lambda q, k, v: fa.flash_block_diffusion(q, k, v, BLOCK)[:, :t])
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(got[1][0][:, t:]) == 0.0)
    text = str(jax.make_jaxpr(lambda q, k, v: fa.flash_block_diffusion(q[:, :t], k, v, BLOCK))(q, k, v))
    assert "_fwd_block_strict_kernel" in text and "name=_fwd_block_kernel" not in text
    with pytest.raises(ValueError, match="hold a row twice"):
        fa.flash_block_diffusion(q[:, :t + 128], k, v, BLOCK)


@pytest.mark.parametrize("dropped", ["own block", "blocks before", "clean copy"])
def test_the_agreement_needs_each_of_the_three_masks(dropped):
    """A plane that lacks one of its three parts is out of the tolerance by
    far: the comparison above can tell."""
    t = 256
    q, k, v = _qkv(2 * t, 32, 1, 1)
    seen = _plane(t, BLOCK)
    if dropped == "own block":
        seen[:t, :t] = np.eye(t, dtype=bool)      # a noised query keeps itself alone
    elif dropped == "blocks before":
        seen[:t, t:] = False
    else:
        seen[t:, t:] = np.tril(np.ones((t, t), bool))   # causal by position, not by block
    got = fa.flash_block_diffusion(q, k, v, BLOCK)
    assert float(jnp.abs(got - _dense(q, k, v, jnp.asarray(seen))).max()) > 1e-2


def test_the_composition_names_its_results_and_its_tiles():
    """Both calls' results carry the names a ``"full"`` remat policy keeps,
    and a record of their shapes the gauge counts tiles from: the causal
    walk's, twice."""
    t = 256
    q, k, v = _qkv(2 * t, 32, 2, 1)
    text = str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_block_diffusion(q, k, v, BLOCK).sum()))(q))
    assert text.count(f"name={fa.FLASH_OUT_NAME}") == 2 and text.count(f"name={fa.FLASH_LSE_NAME}") == 2
    record = f"{fa.FLASH_CALL_NAME}:2:{t}:{t}:32:32:0"
    assert text.count(f"name={record}") == 2
    assert fa.call_tiles(record) == {kind: 2 * n for kind, n in fa.tile_kinds(t, t, 32, 32).items()}


# ---- merging partial results ----------------------------------------------------

def test_two_partial_softmaxes_merge_into_the_whole():
    """Keys split in two sets, each normalised over its own, merged by their
    log-sum-exp: the softmax over both; a side that saw no key weighs 0 and
    leaves no NaN, forward or backward."""
    key = jax.random.PRNGKey(1)
    s = jax.random.normal(key, (3, 16, 24)) * 3.0
    v = jax.random.normal(jax.random.fold_in(key, 1), (3, 24, 8))

    def part(s, v):
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v), jax.nn.logsumexp(s, axis=-1)

    whole, lse = part(s, v)
    o, merged = fa.merge_partials(*part(s[..., :10], v[:, :10]), *part(s[..., 10:], v[:, 10:]))
    np.testing.assert_allclose(np.asarray(o), np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(lse), rtol=1e-6)
    empty = jnp.zeros_like(whole), jnp.full(lse.shape, fa._NEG_INF)

    def through(o1, lse1):
        o, lse = fa.merge_partials(o1, lse1, *empty)
        return (o * 1.5).sum() + lse.sum()

    assert np.array_equal(np.asarray(fa.merge_partials(whole, lse, *empty)[0]), np.asarray(whole))
    grads = jax.grad(through, argnums=(0, 1))(whole, lse)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)


def test_the_ring_merges_its_shards_through_the_same_helper(monkeypatch):
    """``_ring_flash_fwd_impl`` calls ``merge_partials`` once a ring step."""
    from jax.sharding import Mesh, PartitionSpec as P

    calls = []
    real = fa.merge_partials
    monkeypatch.setattr(fa, "merge_partials", lambda *a: (calls.append(1), real(*a))[1])
    mesh = Mesh(np.array(jax.devices()[:2]), ("cp",))
    q, k, v = _qkv(256, 32, 2, 1)
    ring = jax.shard_map(lambda q, k, v: fa.ring_flash_local(q, k, v, "cp", True), mesh=mesh,
                         in_specs=(P(None, "cp"),) * 3, out_specs=P(None, "cp"), check_vma=False)
    out = ring(q, k, v)
    assert calls, "the ring's scan body merged through the helper"
    np.testing.assert_allclose(np.asarray(out), np.asarray(fa.flash_attention(q, k, v)), rtol=2e-5, atol=2e-5)
