"""The plain reference against the program at its family's tiny preset, the
family's weights against the program's own layout, and the lower-precision
control against the limits."""

import numpy as np
import pytest

import bench_tiny
from benchmarks.harness import files, model

CONFIGS = sorted({w["config"] for w in files.load_benchmark_json()["workloads"]})


def _sides(config_name, dtype):
    """Program loss/grads in ``dtype`` and the reference's, same weights."""
    import jax
    import jax.numpy as jnp

    config = files.load_config(config_name)
    family = files.load_family(config["family"])
    tiny = bench_tiny.of_config(config_name)["tiny"]
    sizes = model.sizes_of(config, dict(tiny["config"], compute_dtype=dtype))
    seq = tiny["traffic"]["seq_len"]
    weights = jax.jit(family.make_weights_fn(sizes))(model.seed_key(3))
    toks = jnp.asarray(model.tokens_for(model.vocab_rows(family, sizes), 2, seq, 3, 0, 0))
    got = family.make_grad_step(sizes, seq)(weights, toks)
    want = jax.jit(jax.value_and_grad(
        lambda p, t: family.reference_loss(p, t, sizes, None)))(weights, toks)
    return sizes, got, want


@pytest.mark.parametrize("config_name", CONFIGS)
def test_program_in_float32_is_the_reference(config_name):
    """With float32 compute the program and the reference run the same
    mathematics: what is left is summation order, within the tolerances the
    family's tiny file states (``float32_parity``)."""
    import jax

    tol = bench_tiny.of_config(config_name)["float32_parity"]
    _, (loss, grads), (ref_loss, ref_grads) = _sides(config_name, "float32")
    assert abs(float(loss) - float(ref_loss)) <= tol["loss_rtol"] * abs(float(ref_loss))
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(ref_grads)
    for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=tol["grad_rtol"],
            atol=tol["grad_atol_of_max"] * float(np.abs(np.asarray(r)).max()))


@pytest.mark.parametrize("config_name", CONFIGS)
def test_weights_are_the_programs_layout(config_name):
    """The benchmark's own weights against the abstract tree of the program's
    own initialiser, which the family hands over: structure, shapes, dtypes,
    count."""
    import jax

    config = files.load_config(config_name)
    family = files.load_family(config["family"])
    sizes = model.sizes_of(config, bench_tiny.of_config(config_name)["tiny"]["config"])
    mine = jax.eval_shape(family.make_weights_fn(sizes), jax.random.PRNGKey(0))
    theirs = family.program_init_shapes(sizes)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, mine, theirs)))
    assert family.n_params(sizes) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(theirs))
    assert set(family.STACKED) <= set(mine), "the stacks read layer by layer are top-level groups"


@pytest.mark.parametrize("config_name", CONFIGS)
def test_stacks_are_read_layer_by_layer(config_name):
    """Every leaf of every group the family stacks by layer gives one norm a
    layer, whatever the group is called and however many there are; every
    other leaf gives one."""
    import jax

    from benchmarks.reference.train import leaf_norms

    config = files.load_config(config_name)
    family = files.load_family(config["family"])
    sizes = model.sizes_of(config, bench_tiny.of_config(config_name)["tiny"]["config"])
    weights = jax.eval_shape(family.make_weights_fn(sizes), jax.random.PRNGKey(0))
    norms = jax.eval_shape(lambda w: leaf_norms(w, family.STACKED), weights)
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(weights)[0]}
    assert set(norms) == set(flat)
    for name, leaf in flat.items():
        stacked = name.split("/")[0] in family.STACKED
        assert norms[name].shape == ((leaf.shape[0],) if stacked else (1,)), name
    assert any(name.split("/")[0] in family.STACKED for name in flat)


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
    wide = bench_tiny.preset(cell, "wide")
    low = control_numbers(cell, seed, "float8_e4m3fn", wide)
    assert any(low[k] > limits[k] for k in low), low
    stated = control_numbers(cell, seed, "bfloat16", wide)
    assert all(stated[k] <= limits[k] for k in stated), stated
