"""The SDAR training step (``models/sdar.py``) against the benchmark's plain
reference (``benchmarks/reference/sdar.py``, which imports nothing of the
program) in float32 on seeded weights: loss and every gradient leaf, through
the dense plane and through the flash kernels; and that the agreement is tight
enough to tell when either side drops one of the three masks, shifts the
labels or leaves ``1 / p_b`` out.  (A file of its own so that the suite's
workers share the model's tests.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import sdar

from test_sdar import TINY, _reference_sizes, _tokens


def _both_sides(cfg, t=None, sizes=None):
    from benchmarks.reference.sdar import loss_fn as reference_loss

    t = t or (128 if cfg.attn_impl == "flash" else 64)
    params = sdar.init_params(jax.random.PRNGKey(5), cfg)
    tokens = _tokens(cfg, t=t)
    got = sdar.make_grad_step(cfg)(params, tokens)
    want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_loss(p, t, sizes or _reference_sizes(cfg), None)))(params, tokens)
    return got, want


def _worst(grads, want_grads):
    return max(
        float(np.abs(np.asarray(g) - np.asarray(r)).max() / np.abs(np.asarray(r)).max())
        for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)))


@pytest.mark.parametrize("cfg", [
    TINY,
    dataclasses.replace(TINY, n_layers=4, held_experts=(3, 8, 9, 15), block_length=8),
    dataclasses.replace(TINY, n_layers=2, remat=False, held_experts=tuple(range(16)), mask_token_id=0,
                        n_heads=4, n_kv_heads=1),
    dataclasses.replace(TINY, n_layers=2, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64, attn_impl="flash"),
], ids=["three-layers", "four-layers-blocks-of-8", "every-expert-held-one-kv-head-no-remat",
        "through-the-flash-kernels"])
def test_model_in_float32_is_the_plain_reference(cfg):
    """Loss and every gradient leaf on seeded weights; the reference forms the
    ``[2T, 2T]`` plane a head at a time, runs the experts one at a time and
    takes the loss in blocks of positions."""
    (loss, grads), (want, want_grads) = _both_sides(cfg)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(ref) == 15
    for (path, g), r in zip(flat, ref):
        assert g.shape == r.shape and r.size
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4 * float(np.abs(np.asarray(r)).max()),
            err_msg=jax.tree_util.keystr(path))


# every fault on one side at least, the masks and the weight on both between them (the comparison is
# symmetric; the reference's targets are its tokens, with no seam to shift them at)
@pytest.mark.parametrize("fault,side", [
    ("no own block", "program"), ("no own block", "reference"), ("no blocks before", "reference"),
    ("clean copy causal by position", "program"), ("labels shifted", "program"), ("no 1 / p", "program"),
    ("no 1 / p", "reference")])
def test_the_agreement_needs_the_three_masks_the_unshifted_labels_and_the_weight(monkeypatch, fault, side):
    """The tolerance above is tight enough to tell: a side that drops one of
    the three masks, scores position ``i`` on token ``i + 1`` or leaves ``1 /
    p_b`` out is out of it by far."""
    from benchmarks.reference import sdar as reference

    t = 64

    def broken(plane, *args):
        with jax.ensure_compile_time_eval():
            seen = np.array(plane(*args))
        if fault == "no own block":
            seen[:t, :t] = np.eye(t, dtype=bool)
        elif fault == "no blocks before":
            seen[:t, t:] = False
        else:
            seen[t:, t:] = np.tril(np.ones((t, t), bool))
        return seen

    if fault in ("no own block", "no blocks before", "clean copy causal by position"):
        if side == "program":
            real = sdar.diffusion_mask
            monkeypatch.setattr(sdar, "diffusion_mask", lambda t, block: broken(real, t, block))
        else:
            real = reference.seen_plane
            monkeypatch.setattr(reference, "seen_plane", lambda t, block: jnp.asarray(broken(real, t, block)))
    elif fault == "labels shifted":
        real = sdar._masked_nll
        monkeypatch.setattr(sdar, "_masked_nll", lambda p, x, tokens, w, cfg: real(
            p, x, jnp.roll(tokens, -1, axis=1), w, cfg))
    elif side == "program":
        real = sdar._masked_nll
        monkeypatch.setattr(sdar, "_masked_nll", lambda p, x, tokens, w, cfg: real(
            p, x, tokens, (w > 0).astype(w.dtype), cfg))
    else:
        real = reference.row_noise
        monkeypatch.setattr(reference, "row_noise", lambda row, sizes: (real(row, sizes)[0], jnp.ones(())))
    (loss, grads), (want, want_grads) = _both_sides(dataclasses.replace(TINY, n_layers=2), t=t)
    worst = _worst(grads, want_grads)
    assert abs(float(loss) - float(want)) > 2e-5 * abs(float(want)) or worst > 2e-2
    assert worst > 2e-3, "the stated tolerance, 2e-3 of a leaf's largest entry, fails"


def test_the_reference_reads_the_lower_precision_control():
    from benchmarks.reference.sdar import loss_fn as reference_loss

    params = sdar.init_params(jax.random.PRNGKey(5), TINY)
    tokens = _tokens(TINY)
    sizes = _reference_sizes(TINY)
    exact, half, eighth = (float(jax.jit(lambda p, d=d: reference_loss(p, tokens, sizes, d))(params))
                           for d in (None, "bfloat16", "float8_e4m3fn"))
    assert 0 < abs(half - exact) < abs(eighth - exact) < 0.2 * exact
