"""The plain reference against ``models/transformer.py`` at the tiny preset,
and the lower-precision control against the limits."""

import numpy as np
import pytest

from bench_tiny import TINY, WIDE
from benchmarks.harness import files, model

CONFIGS = sorted({w["config"] for w in files.load_benchmark_json()["workloads"]})


def _sides(config_name, dtype):
    """Program loss/grads in ``dtype`` and the reference's, same weights."""
    import jax
    import jax.numpy as jnp

    config = files.load_config(config_name)
    family = files.load_family(config["family"])
    sizes = model.sizes_of(config, dict(TINY["config"], compute_dtype=dtype))
    seq = TINY["traffic"]["seq_len"]
    weights = jax.jit(family.make_weights_fn(sizes))(model.seed_key(3))
    toks = jnp.asarray(model.tokens_for(sizes["vocab_size"], 2, seq, 3, 0, 0))
    got = family.make_grad_step(sizes, seq)(weights, toks)
    want = jax.jit(jax.value_and_grad(
        lambda p, t: family.reference_loss(p, t, sizes, None)))(weights, toks)
    return sizes, got, want


@pytest.mark.parametrize("config_name", CONFIGS)
def test_program_in_float32_is_the_reference(config_name):
    """With float32 compute the program and the reference run the same
    mathematics: what is left is summation order and the program's
    rms_norm eps of 1e-6 against the published 1e-5 (configs/*.json,
    departures)."""
    import jax

    _, (loss, grads), (ref_loss, ref_grads) = _sides(config_name, "float32")
    assert abs(float(loss) - float(ref_loss)) <= 2e-5 * abs(float(ref_loss))
    for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-3,
                                   atol=2e-4 * float(np.abs(np.asarray(r)).max()))


@pytest.mark.parametrize("config_name", CONFIGS)
def test_weights_are_the_programs_layout(config_name):
    import jax

    from torchft_tpu.models import transformer as tfm

    config = files.load_config(config_name)
    family = files.load_family(config["family"])
    sizes = model.sizes_of(config, TINY["config"])
    cfg = tfm.TransformerConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], n_layers=sizes["num_hidden_layers"])
    mine = jax.eval_shape(family.make_weights_fn(sizes), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                                  mine, theirs)
    assert family.n_params(sizes) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(theirs))


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 12345, 2**32 + 5])
def test_any_seed_makes_weights_and_tokens(seed):
    import jax

    a, b = model.seed_key(seed), model.seed_key(seed + 1)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    toks = model.tokens_for(256, 2, 16, seed, 1, 2)
    assert np.array_equal(toks, model.tokens_for(256, 2, 16, seed, 1, 2))
    assert not np.array_equal(toks, model.tokens_for(256, 2, 16, seed, 0, 2))


CELLS = [w["name"] for w in files.load_benchmark_json()["workloads"]]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(seed):
    """The reference with float8 operands, put in the program's place, fails
    a limit of the cell; with bfloat16 operands — the precision the
    configuration states — it passes the same limits."""
    from benchmarks.control_check import control_numbers

    cell = CELLS[0]
    limits = files.load_limits(cell)
    low = control_numbers(cell, seed, "float8_e4m3fn", WIDE)
    assert any(low[k] > limits[k] for k in low), low
    stated = control_numbers(cell, seed, "bfloat16", WIDE)
    assert all(stated[k] <= limits[k] for k in stated), stated
