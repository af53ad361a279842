"""What the ``lfm2`` family brings beside the members every family has: the
operation count of a model whose token mixer is a convolution (worked by
hand), the convolution operator's operations and bytes, the flash kernels'
work at grouped heads of 64, the device trace read by the program's
``shortconv`` scope, a configuration that holds every published number, and
the compiled step that lets go of the chip's memory before the reference
runs."""

import types

import pytest

from benchmarks.harness import files, model, peaks

FAMILY = files.load_family("lfm2")
C, A = "conv", "full_attention"
SMALL = {
    "hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "layer_types": [C, C, A, C, C, C, A, C], "num_hidden_layers": 6, "num_dense_layers": 2,
    "intermediate_size": 32, "moe_intermediate_size": 8, "num_experts": 2, "router_outputs": 8,
    "num_experts_per_tok": 2, "vocab_size": 64, "compute_dtype": "bfloat16", "param_dtype": "float32",
}
KERNELS = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")
CONFIG = "lfm2-8b-a1b-ep4"


def test_flash_work_is_the_causal_half_at_the_heads_width():
    """3 rows of 10 positions, 4 query heads of 4: 55 pairs a head."""
    work = FAMILY.flash_attn_work(SMALL, 3, 10)
    assert set(work) == set(KERNELS) == set(FAMILY.FLASH_KERNELS)
    pairs = 12 * 55
    assert work["_fwd_kernel"]["flops"] == 2 * 2 * pairs * 4
    assert work["_bwd_kv_kernel"]["flops"] == 2 * 4 * pairs * 4
    assert work["_bwd_q_kernel"]["flops"] == 2 * 3 * pairs * 4
    tile, stat = 12 * 10 * 4 * 2, 12 * 10 * 4
    assert work["_fwd_kernel"]["bytes"] == 4 * tile + stat
    assert work["_bwd_kv_kernel"]["bytes"] == 6 * tile + 2 * stat
    assert work["_bwd_q_kernel"]["bytes"] == 5 * tile + 2 * stat


def test_the_convolution_operators_work_is_two_products_and_the_taps():
    """30 tokens at a width of 16: ``8 N E^2`` for the two projections and ``2
    N E L`` for the taps; the backward twice that; bytes ``h`` in, ``y`` out
    and the weights once (the backward: ``h``, ``dy``, ``dh``, the weights and
    their float32 gradients)."""
    work = FAMILY.shortconv_work(SMALL, 3, 10)
    assert work["forward"]["flops"] == 2 * 30 * (3 * 16 * 16 + 16 * 16) + 2 * 30 * 16 * 3
    assert work["backward"]["flops"] == 2 * work["forward"]["flops"]
    weights = 16 * 48 + 16 * 3 + 16 * 16
    assert work["forward"]["bytes"] == 2 * 30 * 16 * 2 + weights * 2
    assert work["backward"]["bytes"] == 3 * 30 * 16 * 2 + weights * 2 + weights * 4
    # the cell: bound by the operations, 2.8 ms a layer's forward at the chip's peak
    real = FAMILY.shortconv_work(model.sizes_of(files.load_config(CONFIG)), 4, 4096)
    for need in real.values():
        assert need["flops"] / 197e12 > 10 * need["bytes"] / 819e9
    assert peaks.roofline_seconds("TPU v5e", real["forward"]["flops"], real["forward"]["bytes"]) == pytest.approx(
        2.79e-3, rel=0.01)


def test_model_flops_count_convolutions_by_their_products_and_no_shared_expert():
    """Five convolution layers and one attention layer, two dense FFNs, four
    expert layers of which a token meets 2 x 2 / 8 of an expert, the tied
    head once."""
    per_token = (5 * (4 * 16 * 16 + 16 * 3) + (2 * 16 * 16 + 2 * 16 * 8) + 2 * 3 * 16 * 32
                 + 4 * (16 * 8 + (2 * 2 / 8) * 3 * 16 * 8) + 16 * 64)
    attention = 3 * 2 * 2 * (12 * 55) * 4
    assert FAMILY.flops_per_step(SMALL, 3, 10) == 6 * per_token * 30 + attention
    # a longer row buys attention's share back: the convolutions' cost does not grow with it
    sizes = model.sizes_of(files.load_config(CONFIG))
    share = {t: 3 * FAMILY.flash_attn_work(sizes, 16384 // t, t)["_fwd_kernel"]["flops"]
             / FAMILY.flops_per_step(sizes, 16384 // t, t) for t in (4096, 8192, 16384)}
    assert share == pytest.approx({4096: 0.031, 8192: 0.061, 16384: 0.114}, abs=2e-3)
    assert FAMILY.flops_per_step(sizes, 4, 4096) == pytest.approx(26.42e12, rel=1e-3)


def test_the_layer_pattern_and_the_count():
    assert FAMILY.layer_pattern(SMALL) == {"leading_dense": 2, "period": 4}
    assert FAMILY._layers(SMALL) == ["conv", "conv", "attn", "conv", "conv", "conv"]
    assert "conv_L_cache" in FAMILY.WIDTH_KEYS and "router_outputs" in FAMILY.WIDTH_KEYS
    shapes = FAMILY.weight_shapes(SMALL)
    assert "head" not in shapes and not any(name.startswith("shared") for name in shapes["moe"])
    assert shapes["conv"]["conv"] == (5, 16, 3) and shapes["attn"]["wk"] == (1, 16, 8)
    sizes = model.sizes_of(files.load_config(CONFIG))
    assert FAMILY.layer_pattern(sizes) == {"leading_dense": 2, "period": 4}
    assert FAMILY.n_params(sizes) == 568_647_808
    assert FAMILY.n_params(dict(sizes, vocab_size=8192)) == 551_870_592
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=7)) == 568_647_808 + 98_635_904   # layer 6: attention
    whole = dict(sizes, num_hidden_layers=24, num_experts=32, vocab_size=65536)
    assert FAMILY.n_params(whole) == 8_339_929_856


def _run(ops, runs=2):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": FAMILY, "sizes": SMALL, "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 10}}


def _op(op_name, seconds, kernel=None, calls=2, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": calls, "op_name": op_name,
            "kernel": kernel}


OPS = [
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/shortconv/shortconv.proj/dot_general", 0.010),
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/shortconv/shortconv.mix/checkpoint/mul", 0.002),
    _op("jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/shortconv/shortconv.proj/dot_general", 0.010),
    _op("jit(step)/transpose(jvp())/while/body/checkpoint/shortconv/shortconv.mix/checkpoint/rematted_computation/mul", 0.004),
    _op("jit(step)/transpose(jvp())/while/body/checkpoint/shortconv/shortconv.proj/transpose/dot_general", 0.022),
    _op("jit(step)/jvp()/checkpoint/attn/pallas_call", 0.008, "_fwd_kernel", calls=2),
    _op("jit(step)/transpose(jvp())/checkpoint/attn/pallas_call", 0.012, "_bwd_q_kernel"),
    _op("jit(step)/jvp()/checkpoint/attn.proj/dot_general", 0.300),
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/moe.experts/cond/branch_1_fun/checkpoint/moe.gathered/ragged_dot", 0.004),
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/moe.route/top_k", 0.006),
    _op("jit(other)/shortconv/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_the_readers_on_a_run():
    run = _run(OPS)
    # per grad step, of two: both nested scopes, forward, remat and backward; not another program's
    assert files.load_layer_metric("shortconv_ms").read(run) == pytest.approx(1e3 * 0.048 / 2)
    work = FAMILY.shortconv_work(SMALL, 3, 10)

    def floor(part):
        return peaks.roofline_seconds("TPU v5e", work[part]["flops"], work[part]["bytes"])

    # five layers, two grad steps, the forward twice (the trace shows it under remat)
    assert files.load_layer_metric("shortconv_roofline_pct").read(run) == pytest.approx(
        100 * 5 * 2 * (2 * floor("forward") + floor("backward")) / 0.048)
    once = [op for op in OPS if "rematted_computation" not in (op["op_name"] or "")]
    assert files.load_layer_metric("shortconv_roofline_pct").read(_run(once)) == pytest.approx(
        100 * 5 * 2 * (floor("forward") + floor("backward")) / 0.034)
    # the accepted readers on this family's run
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(1e3 * 0.010 / 2)
    assert files.load_layer_metric("moe_masked_path_pct").read(run) == 0.0
    flash = FAMILY.flash_attn_work(SMALL, 3, 10)
    least = sum(2 * peaks.roofline_seconds("TPU v5e", flash[k]["flops"], flash[k]["bytes"])
                for k in ("_fwd_kernel", "_bwd_q_kernel"))
    assert files.load_layer_metric("flash_attn_roofline_pct").read(run) == pytest.approx(100 * least / 0.020)


@pytest.mark.parametrize("name", ["shortconv_ms", "shortconv_roofline_pct"])
def test_a_reader_without_the_family_or_a_device_reads_nothing_or_zero(name):
    reader = files.load_layer_metric(name)
    other = types.SimpleNamespace(scope_ms=FAMILY.scope_ms, scope_rows=FAMILY.scope_rows)  # no such operator
    assert reader.read(dict(_run(OPS), family=other)) is None
    assert reader.read(dict(_run(OPS), family=files.load_family("kimi_linear"))) is None
    assert reader.read(dict(_run(OPS), family=types.SimpleNamespace())) is None
    assert reader.read({"records": [], "trace": {"module_seconds": {}}, "grad_module": "jit_step",
                        "family": FAMILY}) is None                                # a run without operations
    # a rehearsal on the CPU: the program ran, no device did
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 10}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0
    # a program without the scope reads 0 seconds and raises nothing
    bare = [_op("jit(step)/jvp()/attn.proj/dot_general", 0.3)]
    assert reader.read(_run(bare)) == 0.0


def test_sizes_the_program_cannot_express_are_refused():
    config = files.load_config(CONFIG)
    sizes = model.sizes_of(config)
    FAMILY.check(sizes)
    for over, match in (
            ({"conv_bias": True}, "expresses"),
            ({"tie_word_embeddings": False}, "expresses"),
            ({"norm_topk_prob": False}, "expresses"),
            ({"layer_types": [C, C, "sliding_attention"] * 8}, "conv or full_attention"),
            ({"layer_types": [C, C]}, "conv or full_attention"),
            ({"held_expert_ids": [0, 1, 2, 3, 4, 5, 6, 40]}, "held_expert_ids"),
            ({"num_key_value_heads": 5}, "multiple"),
            ({"num_attention_heads": 30}, "hidden_size over")):
        with pytest.raises(ValueError, match=match):
            FAMILY.check(dict(sizes, **over))


def test_the_configuration_holds_every_published_number():
    """Every key of the published ``config.json`` under its own name, but for
    the three cuts, which state their published values; what it has no key
    for is under ``assumed``."""
    import json

    config = files.load_config(CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
        "layer_types": [C, C, A] + [C, C, C, A] * 4 + [C, C, A, C, C]}
    entry = files.load_config_entry(config["name"])
    cut = set(entry["reduced"])
    assert cut == set(config["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in published.items():
        assert (config["published"] if key in cut else config)[key] == value, key
    assert len(config["layer_types"]) == 24 and config["layer_types"].count(A) == 6
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (6, 8, 16384)
    assert config["router_outputs"] == 32 and config["deployment"]["chips_sharing_a_layer"] == 4
    assumed = config["assumed"]
    assert assumed["seq_len"] == 4096 and assumed["tie_word_embeddings"] is True and assumed["head_dim"] == 64
    assert assumed["held_expert_ids"] == list(range(8)) and assumed["remat_policy"] == "full"
    for key in ("rope", "qk_norm", "conv", "expert_bias", "router_eps", "shared_expert", "expert_slack_why"):
        assert assumed[key], key
    assert "modeling_lfm2_moe.py" in assumed["_why"]
    json.dumps(config)


def test_the_compiled_step_is_released_before_the_reference_runs():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config(CONFIG)
    sizes = model.sizes_of(config, bench_tiny.of_family("lfm2")["tiny"]["config"])
    weights = jax.jit(FAMILY.make_weights_fn(sizes))(model.seed_key(1))
    tokens = jnp.asarray(model.tokens_for(256, 1, 64, 1, 0, 0))
    step = FAMILY.make_grad_step(sizes, 64)
    assert step.__name__ == "step"
    compiled = step.lower(weights, tokens).compile()
    loss, _ = compiled(weights, tokens)
    text, analysis = compiled.as_text(), compiled.memory_analysis()
    stats = FAMILY.make_routing_stats(sizes)(weights, tokens)
    assert stats["assignments"].shape == (4, 4) and int(stats["assignments"].sum()) <= 4 * 64 * 4
    want = FAMILY.reference_loss(weights, tokens, sizes, None)   # releases
    assert compiled._executable is None
    assert compiled.as_text() == text and compiled.memory_analysis() is analysis
    assert abs(float(loss) - float(want)) < 0.02 * abs(float(want))
    with pytest.raises(TypeError):
        compiled(weights, tokens)  # the window is over
