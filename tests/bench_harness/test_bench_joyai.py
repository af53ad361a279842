"""What the ``joyai`` family brings beside the members every family has: the
operation count of a model with two prediction depths (worked by hand), the
flash kernels' work at 192 / 128 in every block, the device trace read by the
program's ``mla`` and ``mtp`` scopes (the module's nested ones counted by
both), the module's group read as a layer, and the compiled step that lets go
of the chip's memory before the reference runs."""

import types

import pytest

from benchmarks.harness import files, model, peaks

FAMILY = files.load_family("joyai")
SMALL = {
    "hidden_size": 16, "num_attention_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 32, "moe_intermediate_size": 8, "n_routed_experts": 2, "router_outputs": 8,
    "num_experts_per_tok": 2, "num_nextn_predict_layers": 1, "vocab_size": 64, "compute_dtype": "bfloat16",
}
KERNELS = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")


def test_flash_work_is_the_causal_half_at_unequal_widths():
    """3 rows of 10 positions, 4 heads: 55 pairs a head; a product with K or
    Q costs 12 (nope + rope), one with V or dO 8."""
    work = FAMILY.flash_attn_work(SMALL, 3, 10)
    assert set(work) == set(KERNELS) == set(FAMILY.FLASH_KERNELS)
    pairs = 12 * 55
    assert work["_fwd_kernel"]["flops"] == 2 * pairs * (12 + 8)            # S = Q K^T, P V
    assert work["_bwd_kv_kernel"]["flops"] == 2 * pairs * (2 * 12 + 2 * 8)  # S again, dV, dP, dK
    assert work["_bwd_q_kernel"]["flops"] == 2 * pairs * (2 * 12 + 8)       # S again, dP, dQ
    # the cell: operations bound every one of the three at heads of 192 / 128
    real = FAMILY.flash_attn_work(model.sizes_of(files.load_config("joyai-llm-flash-48b-a3b-ep32")), 2, 8192)
    for need in real.values():
        assert need["flops"] / 197e12 > need["bytes"] / 819e9


def test_model_flops_count_the_module_its_head_and_the_merge():
    """Three trunk layers (one dense) and the module: four blocks of latent
    attention, three expert layers, ``W_eh``, the head twice."""
    per_mla = 16 * 12 + 12 * 4 * 12 + 16 * (8 + 4) + 8 * 4 * 16 + 4 * 8 * 16
    per_moe = 16 * 8 + (1 + 2 * 2 / 8) * 3 * 16 * 8
    per_token = 4 * per_mla + 3 * 16 * 32 + 3 * per_moe + 2 * 16 * 16 + 2 * 16 * 64
    attention = 3 * 4 * 2 * (12 * 55) * (12 + 8)
    assert FAMILY.flops_per_step(SMALL, 3, 10) == 6 * per_token * 30 + attention
    # without the module: a block, an expert layer, the merge and one pass through the head less
    less = FAMILY.flops_per_step(dict(SMALL, num_nextn_predict_layers=0), 3, 10)
    assert FAMILY.flops_per_step(SMALL, 3, 10) - less == 6 * 30 * (
        per_mla + per_moe + 2 * 16 * 16 + 16 * 64) + attention / 4
    # the cell's: attention is 46 % of the step, the module with its head 18 %
    sizes = model.sizes_of(files.load_config("joyai-llm-flash-48b-a3b-ep32"))
    whole = FAMILY.flops_per_step(sizes, 2, 8192)
    assert whole == pytest.approx(62.44e12, rel=1e-3)
    assert 3 * 7 * FAMILY.flash_attn_work(sizes, 2, 8192)["_fwd_kernel"]["flops"] / whole == pytest.approx(0.462, abs=2e-3)
    module = whole - FAMILY.flops_per_step(dict(sizes, num_nextn_predict_layers=0), 2, 8192)
    assert module / whole == pytest.approx(0.18, abs=0.01)


def test_the_layer_pattern_and_the_modules_group():
    assert FAMILY.layer_pattern(SMALL) == {"leading_dense": 1, "period": 1}
    assert "mtp" in FAMILY.STACKED and "num_nextn_predict_layers" in FAMILY.WIDTH_KEYS
    shapes = FAMILY.weight_shapes(SMALL)
    assert all(shape[0] == 1 for shape in shapes["mtp"].values()), "the module is read as a group of one layer"
    assert set(shapes["mtp"]) == set(shapes["mla"]) | set(shapes["moe"]) | {"e_norm", "h_norm", "w_eh", "out_norm"}
    sizes = model.sizes_of(files.load_config("joyai-llm-flash-48b-a3b-ep32"))
    assert FAMILY.n_params(sizes) == 561_039_360
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=5)) == 491_696_128


def _run(ops, runs=2):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": FAMILY, "sizes": SMALL, "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 10}}


def _op(op_name, seconds, kernel=None, calls=2, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": calls, "op_name": op_name,
            "kernel": kernel}


OPS = [
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/mla/pallas_call", 0.010, "_fwd_kernel", calls=12),
    _op("jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/mla/mla.rope/mul", 0.006),
    _op("jit(step)/transpose(jvp())/while/body/checkpoint/mla/pallas_call", 0.020, "_bwd_kv_kernel", calls=6),
    _op("jit(step)/jvp(mtp)/checkpoint/mla/pallas_call", 0.004, "_fwd_kernel", calls=4),
    _op("jit(step)/transpose(jvp(mtp))/checkpoint/mla/dot_general", 0.008),
    _op("jit(step)/jvp(mtp)/mtp.merge/checkpoint/dot_general", 0.002),
    _op("jit(step)/jvp(mtp)/checkpoint/moe.experts/cond/branch_0_fun/checkpoint/moe.gathered/ragged_dot", 0.003),
    _op("jit(step)/jvp(mtp)/head/while/body/checkpoint/dot_general", 0.005),
    _op("jit(step)/jvp()/head/while/body/checkpoint/dot_general", 0.005),
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/moe.route/top_k", 0.007),
    _op("jit(other)/mla/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_the_readers_on_a_run():
    run = _run(OPS)
    # per grad step, of two: every block's latent attention, the module's among them
    assert files.load_layer_metric("mla_ms").read(run) == pytest.approx(1e3 * (0.010 + 0.006 + 0.020 + 0.004 + 0.008) / 2)
    # the module: its block, its merge, its experts, its pass through the head
    assert files.load_layer_metric("mtp_ms").read(run) == pytest.approx(1e3 * (0.004 + 0.008 + 0.002 + 0.003 + 0.005) / 2)
    # the accepted readers see the module's nested scopes as their own
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(1e3 * (0.003 + 0.007) / 2)
    assert files.load_layer_metric("moe_masked_path_pct").read(run) == 0.0
    work = FAMILY.flash_attn_work(SMALL, 3, 10)

    def floor(name, calls):
        return calls * peaks.roofline_seconds("TPU v5e", work[name]["flops"], work[name]["bytes"])

    assert files.load_layer_metric("flash_attn_roofline_pct").read(run) == pytest.approx(
        100 * (floor("_fwd_kernel", 16) + floor("_bwd_kv_kernel", 6)) / 0.034)


@pytest.mark.parametrize("name", ["mla_ms", "mtp_ms"])
def test_a_reader_without_scopes_or_a_device_reads_nothing_or_zero(name):
    reader = files.load_layer_metric(name)
    assert reader.read(dict(_run(OPS), family=types.SimpleNamespace())) is None  # a family that reads no scopes
    assert reader.read({"records": [], "trace": {"module_seconds": {}}, "grad_module": "jit_step",
                        "family": FAMILY}) is None                                # a run without operations
    # a rehearsal on the CPU: the program ran, no device did
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 10}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0
    # a program without the scope (the parent's, on another family's cell) reads 0 seconds and raises nothing
    bare = [_op("jit(step)/jvp()/attn.proj/dot_general", 0.3)]
    assert reader.read(_run(bare)) == 0.0


def test_sizes_the_program_cannot_express_are_refused():
    config = files.load_config("joyai-llm-flash-48b-a3b-ep32")
    sizes = model.sizes_of(config)
    FAMILY.check(sizes)
    for over, match in (
            ({"scoring_func": "softmax"}, "expresses"),
            ({"num_nextn_predict_layers": 2}, "expresses"),
            ({"rope_interleave": False}, "expresses"),
            ({"rope_scaling": {"type": "yarn"}}, "expresses"),
            ({"held_expert_ids": [0, 1, 2, 3, 4, 5, 6, 300]}, "held_expert_ids"),
            ({"num_key_value_heads": 4}, "key-value head a query head"),
            ({"qk_rope_head_dim": 63}, "even")):
        with pytest.raises(ValueError, match=match):
            FAMILY.check(dict(sizes, **over))


def test_the_configuration_holds_every_published_number():
    """Every number of the published ``config.json`` under its own key, but
    for the three cuts, which state their published values."""
    import json

    config = files.load_config("joyai-llm-flash-48b-a3b-ep32")
    published = {
        "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048, "intermediate_size": 7168,
        "kv_lora_rank": 512, "max_position_embeddings": 131072, "moe_intermediate_size": 768, "moe_layer_freq": 1,
        "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "topk_group": 1, "v_head_dim": 128, "vocab_size": 129280}
    cut = set(files.load_config_entry(config["name"])["reduced"])
    assert cut == set(config["published"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in published.items():
        assert (config["published"] if key in cut else config)[key] == value, key
    assert config["router_outputs"] == 256 and config["deployment"]["chips_sharing_a_layer"] == 32
    assert config["assumed"]["mtp_loss_weight"] == 0.3 and config["assumed"]["seq_len"] == 8192
    json.dumps(config)


def test_the_compiled_step_is_released_before_the_reference_runs():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config("joyai-llm-flash-48b-a3b-ep32")
    sizes = model.sizes_of(config, bench_tiny.of_family("joyai")["tiny"]["config"])
    weights = jax.jit(FAMILY.make_weights_fn(sizes))(model.seed_key(1))
    tokens = jnp.asarray(model.tokens_for(256, 1, 64, 1, 0, 0))
    step = FAMILY.make_grad_step(sizes, 64)
    assert step.__name__ == "step"
    compiled = step.lower(weights, tokens).compile()
    loss, _ = compiled(weights, tokens)
    text, analysis = compiled.as_text(), compiled.memory_analysis()
    stats = FAMILY.make_routing_stats(sizes)(weights, tokens)
    # two expert layers of the trunk, then the module's
    assert stats["assignments"].shape == (3, 4) and int(stats["assignments"].sum()) <= 3 * 64 * 4
    want = FAMILY.reference_loss(weights, tokens, sizes, None)   # releases
    assert compiled._executable is None
    assert compiled.as_text() == text and compiled.memory_analysis() is analysis
    assert abs(float(loss) - float(want)) < 0.02 * abs(float(want))
    with pytest.raises(TypeError):
        compiled(weights, tokens)  # the window is over
