"""Full remat keeps the flash kernel's two results (``models/transformer.py``
``_remat``; the names come from ``ops/flash_attention.py`` ``_flash_fwd``): in
every family's grad step the forward kernel runs once a layer where a
checkpoint that keeps nothing runs it twice, the numbers are the same bits, and
the gauge ``torchft_remat_kept_bytes`` reads what is kept off the traced
program.  All on the CPU interpreter at tiny sizes."""

import collections
import dataclasses
import re

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import afmoe, joyai, kimi_linear, transformer
from torchft_tpu.utils import metrics

B, T = 2, 128
S, F = "sliding_attention", "full_attention"

# family -> (module, configuration through the flash kernels, layers through
# the causal kernels, layers through the windowed ones, heads, value width)
FAMILIES = {
    "dense": (transformer, transformer.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, n_layers=3, max_seq_len=T,
        dtype=jnp.float32, attn_impl="flash"), 3, 0, 4, 16),
    "kimi-linear": (kimi_linear, kimi_linear.KimiLinearConfig(
        vocab_size=128, d_model=32, n_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
        first_k_dense=1, n_heads=2, kda_heads=2, kda_head_dim=16, kda_gate_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=64, d_expert=16,
        n_routed_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32,
        attn_impl="flash"), 1, 0, 2, 16),
    "afmoe-window": (afmoe, afmoe.AfmoeConfig(
        vocab_size=128, d_model=32, n_layers=6, layer_types=(S, S, S, F), num_dense_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, sliding_window=24, d_ff=64, d_expert=16, n_routed_experts=16,
        experts_per_token=4, held_experts=(0, 1, 2, 3), dtype=jnp.float32, attn_impl="flash"),
        1, 5, 4, 8),
    "joyai-module": (joyai, joyai.JoyAIConfig(
        vocab_size=128, d_model=32, n_layers=3, first_k_dense=1, n_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        d_ff=64, d_expert=16, n_routed_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3),
        dtype=jnp.float32, attn_impl="flash"), 4, 0, 2, 16),  # three layers and the module's block
}


def _inputs(family):
    module, cfg = FAMILIES[family][:2]
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    return module, cfg, params, tokens


def kernel_calls(jaxpr, layers=1):
    """``pallas_call``s of a jaxpr by the call's ``name``, nested jaxprs
    walked, a scanned one counted once a layer."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += layers
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_calls(sub, layers * eqn.params.get("length", 1))
    return found


def _keep_nothing(monkeypatch, module):
    """The parent's ``"full"``: ``jax.checkpoint`` with no policy."""
    monkeypatch.setattr(module, "_remat", lambda fn, cfg: jax.checkpoint(fn))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_forward_kernel_runs_once_a_layer(family, monkeypatch):
    module, cfg, params, tokens = _inputs(family)
    causal, window = FAMILIES[family][2:4]
    kept = kernel_calls(module.make_grad_step(cfg).trace(params, tokens).jaxpr.jaxpr)
    assert kept == {name: n for name, n in {
        "_fwd_kernel": causal, "_bwd_kv_kernel": causal, "_bwd_q_kernel": causal,
        "_fwd_window_kernel": window, "_bwd_kv_window_kernel": window, "_bwd_q_window_kernel": window,
    }.items() if n}
    _keep_nothing(monkeypatch, module)
    again = kernel_calls(module.make_grad_step(cfg).trace(params, tokens).jaxpr.jaxpr)
    # the backward kernels as often as with nothing kept, the forward half as often
    assert again == kept + collections.Counter(
        {name: n for name, n in kept.items() if name.startswith("_fwd")})


@pytest.mark.parametrize("family", FAMILIES)
def test_loss_and_gradients_are_the_bits_of_a_checkpoint_that_keeps_nothing(family, monkeypatch):
    module, cfg, params, tokens = _inputs(family)
    loss, grads = module.make_grad_step(cfg)(params, tokens)
    _keep_nothing(monkeypatch, module)
    loss0, grads0 = module.make_grad_step(cfg)(params, tokens)
    assert np.isfinite(float(loss)) and np.asarray(loss).tobytes() == np.asarray(loss0).tobytes()
    leaves, leaves0 = jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads0)
    assert len(leaves) == len(leaves0) and any(np.asarray(leaf).any() for leaf in leaves)
    for a, b in zip(leaves, leaves0):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _gauge_after_building(module, cfg, params, tokens):
    metrics.REMAT_KEPT_BYTES.set(-1)
    module.make_grad_step(cfg).trace(params, tokens)
    return metrics.REMAT_KEPT_BYTES.get()


@pytest.mark.parametrize("family", FAMILIES)
def test_the_gauge_reads_the_kept_bytes_off_the_program(family):
    module, cfg, params, tokens = _inputs(family)
    causal, window, heads, dv = FAMILIES[family][2:]
    # a layer keeps B T H Dv of the activations' width and B H T of float32
    a_layer = B * T * heads * dv * 4 + B * heads * T * 4
    assert _gauge_after_building(module, cfg, params, tokens) == (causal + window) * a_layer


@pytest.mark.parametrize("change", [
    {"attn_impl": "dense"}, {"remat_policy": "dots"}, {"remat": False}], ids=lambda c: "-".join(map(str, *c.items())))
@pytest.mark.parametrize("family", FAMILIES)
def test_the_gauge_reads_zero_where_full_remat_has_no_flash_call(family, change):
    module, cfg, params, tokens = _inputs(family)
    assert _gauge_after_building(module, dataclasses.replace(cfg, **change), params, tokens) == 0


def test_the_gauge_in_bfloat16_is_the_issues_formula():
    """``B T H Dv`` x 2 B + ``B H T`` x 4 B a layer."""
    module, cfg, params, tokens = _inputs("dense")
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert _gauge_after_building(module, cfg, params, tokens) == 3 * (B * T * 4 * 16 * 2 + B * 4 * T * 4)


def test_on_a_mesh_each_shard_keeps_its_part():
    """The flash call sits in a ``shard_map`` under the checkpoint: the policy
    reaches into it, and the gauge reads a device's share."""
    from jax.sharding import Mesh, NamedSharding

    module, cfg, params, tokens = _inputs("dense")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    params = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)), params,
        module.param_specs(cfg, mesh))
    tokens = jax.device_put(tokens, NamedSharding(mesh, module.batch_spec(cfg, mesh)))
    traced = module.make_grad_step(cfg, mesh).trace(params, tokens)
    assert kernel_calls(traced.jaxpr.jaxpr) == {"_fwd_kernel": 3, "_bwd_kv_kernel": 3, "_bwd_q_kernel": 3}
    assert metrics.REMAT_KEPT_BYTES.get() == 3 * (B * T * 4 * 16 * 4 + B * 4 * T * 4) // 4


def test_dots_is_what_it_was(monkeypatch):
    """``"dots"`` keeps matrix products and not the kernel's results: the
    forward kernel twice a layer, the program of ``dots_saveable`` alone."""
    module, cfg, params, tokens = _inputs("dense")
    cfg = dataclasses.replace(cfg, remat_policy="dots")
    step = module.make_grad_step(cfg)
    assert kernel_calls(step.trace(params, tokens).jaxpr.jaxpr) == {
        "_fwd_kernel": 6, "_bwd_kv_kernel": 3, "_bwd_q_kernel": 3}
    text = str(step.trace(params, tokens).jaxpr)
    monkeypatch.setattr(module, "_remat", lambda fn, cfg: jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_saveable))
    assert str(module.make_grad_step(cfg).trace(params, tokens).jaxpr) == text


def _residuals(capsys, fn, *args):
    """``jax.ad_checkpoint.print_saved_residuals`` as ``[(shape text, origin)]``."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    return [tuple(line.split(" ", 1)) for line in capsys.readouterr().out.splitlines()]


@pytest.mark.parametrize("attn_impl", ["flash", "dense"])
def test_what_one_block_keeps(attn_impl, capsys):
    """What a checkpoint without a policy keeps (its arguments, and nothing
    else of an activation's size), and under the flash kernels the two named
    results behind that."""
    module, cfg, params, _ = _inputs("dense")
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    layer = jax.tree_util.tree_map(lambda leaf: leaf[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.d_model), jnp.float32)
    block = module._make_block(cfg, None)

    def run(remat):
        return lambda x, layer: remat(block)(x, layer, jnp.arange(T))[0]

    kept = _residuals(capsys, run(lambda fn: module._remat(fn, cfg)), x, layer)
    nothing = _residuals(capsys, run(jax.checkpoint), x, layer)
    for shape, origin in nothing:
        elements = int(np.prod([int(n) for n in re.findall(r"\d+", shape.split("[")[1])]))
        assert origin.startswith("from the argument") or elements <= T, (shape, origin)
    assert kept[:len(nothing)] == nothing
    # (the output is read from the named rows, so jax lists it by the
    # ``reduce_precision`` it puts between the two uses, not by its name)
    assert [(shape, "flash_attention.py" in origin) for shape, origin in kept[len(nothing):]] == (
        [] if attn_impl == "dense" else [(f"f32[{B},{T},64]", True), (f"f32[{B * 4},{T}]", True)])
    assert ("named 'flash_attn_lse'" in kept[-1][1]) == (attn_impl == "flash")
