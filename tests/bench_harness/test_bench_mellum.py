"""What the ``mellum`` family brings beside the members every family has: the
operation count of a model with experts in every layer and none shared
(worked by hand), the flash kernels' work at a window of 1024 in rows of 8192,
the device trace read by the program's ``moe.route`` scope (``moe_route_ms``)
and by the accepted readers, a configuration that holds every published
number, and the compiled step that lets go of the chip's memory before the
reference runs."""

import types

import pytest

from benchmarks.harness import files, model, peaks

FAMILY = files.load_family("mellum")
S, F = "sliding_attention", "full_attention"
SMALL = {
    "hidden_size": 16, "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "sliding_window": 4, "layer_types": [S, S, S, F, S, S, S, F], "num_hidden_layers": 4,
    "moe_intermediate_size": 8, "num_experts": 4, "router_outputs": 8, "num_experts_per_tok": 2,
    "vocab_size": 64, "compute_dtype": "bfloat16", "param_dtype": "float32",
}
WINDOW = ("_fwd_window_kernel", "_bwd_kv_window_kernel", "_bwd_q_window_kernel")
GLOBAL = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")
CONFIG, CELL = "mellum2-12b-a2.5b-ep4", "mellum2-ddp1-steady"
SPARSE_CELLS = ("kimi-linear-ddp1-steady", "trinity-mini-ddp1-steady", "joyai-flash-ddp1-steady",
                "lfm2-ddp1-steady", CELL)


def test_flash_work_counts_a_window_layer_by_its_band():
    """3 rows of 10 positions, 4 heads of 8, a window of 4: a head's band is
    1 + 2 + 3 + 7 x 4 = 34 pairs where the causal half is 55."""
    work = FAMILY.flash_attn_work(SMALL, 3, 10)
    assert set(work) == set(WINDOW + GLOBAL) == set(FAMILY.FLASH_KERNELS + FAMILY.FLASH_WINDOW_KERNELS)
    for names, pairs in ((GLOBAL, 12 * 55), (WINDOW, 12 * 34)):
        fwd, bwd_kv, bwd_q = (work[name] for name in names)
        assert fwd["flops"] == 2 * 2 * pairs * 8
        assert bwd_kv["flops"] == 2 * 4 * pairs * 8
        assert bwd_q["flops"] == 2 * 3 * pairs * 8
        tile, stat = 12 * 10 * 8 * 2, 12 * 10 * 4
        assert fwd["bytes"] == 4 * tile + stat
        assert bwd_kv["bytes"] == 6 * tile + 2 * stat
        assert bwd_q["bytes"] == 5 * tile + 2 * stat
    # the cell: at a window of 1024 in rows of 8192 a window layer does 23.4 % of a global layer's pairs
    real = FAMILY.flash_attn_work(model.sizes_of(files.load_config(CONFIG)), 2, 8192)
    assert real["_fwd_window_kernel"]["flops"] / real["_fwd_kernel"]["flops"] == pytest.approx(0.2344, abs=2e-4)
    # at heads of 128 the operations bound every one of the six
    for need in real.values():
        assert need["flops"] / 197e12 > need["bytes"] / 819e9


def test_model_flops_count_experts_in_every_layer_and_no_shared_expert():
    """Three window layers and one global, four projections each, four expert
    layers of which a token meets 2 x 4 / 8 of an expert, an untied head."""
    per_token = 4 * (2 * 16 * 32 + 2 * 16 * 16 + 16 * 8 + (2 * 4 / 8) * 3 * 16 * 8) + 16 * 64
    attention = 3 * 2 * 2 * 12 * 8 * (55 + 3 * 34)
    assert FAMILY.flops_per_step(SMALL, 3, 10) == 6 * per_token * 30 + attention
    sizes = model.sizes_of(files.load_config(CONFIG))
    whole = FAMILY.flops_per_step(sizes, 2, 8192)
    assert whole == 6 * 163_381_248 * 16384 + 5_618_370_871_296 == pytest.approx(21.68e12, rel=1e-3)
    # by operations: projections 39 %, attention 26 %, routed experts 22 %, head 13 %
    tokens = 6 * 16384
    share = {"projections": 4 * 21_233_664 * tokens / whole, "head": 2304 * 12288 * tokens / whole,
             "attention": 5_618_370_871_296 / whole, "experts": 4 * 2 * 3 * 2304 * 896 * tokens / whole}
    assert share == pytest.approx({"projections": 0.385, "head": 0.128, "attention": 0.259, "experts": 0.225},
                                  abs=2e-3)
    # at a quarter of the vocabulary (the four chips' share, which the reference's memory refused): 24.46 TFLOP
    assert FAMILY.flops_per_step(dict(sizes, vocab_size=24576), 2, 8192) == pytest.approx(24.46e12, rel=1e-3)


def test_the_layer_pattern_and_the_count():
    assert FAMILY.layer_pattern(SMALL) == {"leading_dense": 0, "period": 4}
    assert FAMILY._layers(SMALL) == ["local", "local", "local", "global"]
    assert {"head_dim", "router_outputs", "num_experts_per_tok"} <= set(FAMILY.WIDTH_KEYS)
    assert FAMILY.STACKED == ("local", "global", "moe")
    shapes = FAMILY.weight_shapes(SMALL)
    assert "dense" not in shapes and not any(name.startswith("shared") for name in shapes["moe"])
    assert shapes["head"] == (16, 64) and shapes["moe"]["router"] == (4, 16, 8)
    assert shapes["local"]["wq"] == (3, 16, 32) and shapes["global"]["wk"] == (1, 16, 16)
    sizes = model.sizes_of(files.load_config(CONFIG))
    assert FAMILY.layer_pattern(sizes) == {"leading_dense": 0, "period": 4}
    assert FAMILY.n_params(sizes) == 538_531_072 == 4 * 120_476_416 + 2 * 28_311_552 + 2304
    assert FAMILY.n_params(dict(sizes, vocab_size=24576)) == 595_154_176         # a quarter: the four chips' share
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=5, vocab_size=24576)) == 715_630_592   # 17.2 GB there
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=8, num_experts=8)) == 624_075_008   # 14.98 GB there
    whole = dict(sizes, num_hidden_layers=28, num_experts=64, vocab_size=98304)
    assert FAMILY.n_params(whole) == 12_149_923_072


def _run(ops, runs=2, family=FAMILY):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": family, "sizes": SMALL, "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 10}}


def _op(op_name, seconds, kernel=None, calls=2, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": calls, "op_name": op_name,
            "kernel": kernel}


OPS = [
    _op("jit(step)/jvp()/checkpoint/moe.route/moe.route.score/dot_general", 0.004),
    _op("jit(step)/jvp()/checkpoint/moe.route/moe.route.score/top_k", 0.006),
    _op("jit(step)/jvp()/checkpoint/moe.route/moe.route.place/cumsum", 0.010),
    _op("jit(step)/transpose(jvp())/checkpoint/rematted_computation/moe.route/moe.route.place/cumsum", 0.010),
    _op("jit(step)/transpose(jvp())/checkpoint/moe.route/moe.route.score/transpose/dot_general", 0.002),
    _op("jit(step)/jvp()/checkpoint/moe.experts/cond/branch_1_fun/checkpoint/moe.gathered/ragged_dot", 0.040),
    _op("jit(step)/jvp()/checkpoint/attn.local/attn.rope/mul", 0.002),
    _op("jit(step)/jvp()/checkpoint/attn.local/pallas_call", 0.010, "_fwd_window_kernel", calls=6),
    _op("jit(step)/jvp()/checkpoint/attn.global/attn.rope/mul", 0.001),
    _op("jit(step)/transpose(jvp())/checkpoint/attn.global/pallas_call", 0.012, "_bwd_q_kernel"),
    _op("jit(step)/jvp()/checkpoint/attn.proj/dot_general", 0.300),
    _op("jit(other)/moe.route/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_the_readers_on_a_run():
    run = _run(OPS)
    # per grad step, of two: both nested scopes, forward, remat and backward; not another program's
    assert files.load_layer_metric("moe_route_ms").read(run) == pytest.approx(1e3 * 0.032 / 2)
    # the accepted readers on this family's run: route and experts together; the rotary inside its layer's scope
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(1e3 * 0.072 / 2)
    assert files.load_layer_metric("moe_masked_path_pct").read(run) == 0.0
    assert files.load_layer_metric("attn_local_ms").read(run) == pytest.approx(1e3 * 0.012 / 2)
    assert files.load_layer_metric("attn_global_ms").read(run) == pytest.approx(1e3 * 0.013 / 2)
    work = FAMILY.flash_attn_work(SMALL, 3, 10)

    def floor(name, calls):
        return calls * peaks.roofline_seconds("TPU v5e", work[name]["flops"], work[name]["bytes"])

    assert files.load_layer_metric("flash_window_roofline_pct").read(run) == pytest.approx(
        100 * floor("_fwd_window_kernel", 6) / 0.010)
    assert files.load_layer_metric("flash_attn_roofline_pct").read(run) == pytest.approx(
        100 * (floor("_fwd_window_kernel", 6) + floor("_bwd_q_kernel", 2)) / 0.022)


def test_the_route_reader_without_a_family_a_device_or_the_scope():
    reader = files.load_layer_metric("moe_route_ms")
    assert reader.read(_run(OPS, family=types.SimpleNamespace())) is None          # a family that reads no scopes
    assert reader.read({"records": [], "trace": {"module_seconds": {}}, "grad_module": "jit_step",
                        "family": FAMILY}) is None                                # a run without operations
    # the four sparse families that run the same scope read it through their own ``scope_ms``
    for name in ("kimi_linear", "afmoe", "joyai", "lfm2"):
        assert reader.read(_run(OPS, family=files.load_family(name))) == pytest.approx(16.0)
    # a rehearsal on the CPU: the program ran, no device did
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 10}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0
    # a program without the scope (or without its two inner names, as the parent's) reads what is there
    assert reader.read(_run([_op("jit(step)/jvp()/attn.proj/dot_general", 0.3)])) == 0.0
    parent = [_op("jit(step)/jvp()/checkpoint/moe.route/top_k", 0.006)]
    assert reader.read(_run(parent)) == pytest.approx(3.0)


def test_the_metric_is_reported_by_the_five_sparse_cells():
    entry = [m for m in files.load_benchmark_json()["per_layer"] if m["name"] == "moe_route_ms"]
    assert len(entry) == 1 and tuple(entry[0]["workloads"]) == SPARSE_CELLS
    assert (entry[0]["unit"], entry[0]["better"], entry[0]["moves"], entry[0]["layer"], entry[0]["source"]) == (
        "ms", "lower", "tokens_per_s", "L1 model step", "program_span")
    reports = files.reported("per_layer", CELL)
    for name in ("moe_route_ms", "moe_experts_ms", "moe_masked_path_pct", "attn_local_ms", "attn_global_ms",
                 "flash_window_roofline_pct", "flash_attn_roofline_pct", "grad_step_mfu_pct", "fwdbwd_ms"):
        assert name in reports, name
    assert set(files.reported("end_to_end", CELL)) == {"tokens_per_s", "setup_s"}
    cell = files.load_workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "steady-1x2x8192", 1)


def test_sizes_the_program_cannot_express_are_refused():
    config = files.load_config(CONFIG)
    sizes = model.sizes_of(config)
    FAMILY.check(sizes)
    rules = sizes["rope_parameters"]
    for over, match in (
            ({"mlp_layer_types": ["sparse"] * 27 + ["dense"]}, "no dense FFN"),
            ({"mlp_layer_types": ["sparse"] * 4}, "no dense FFN"),
            ({"tie_word_embeddings": True}, "expresses"),
            ({"norm_topk_prob": False}, "expresses"),
            ({"attention_bias": True}, "expresses"),
            ({"rope_parameters": dict(rules, full_attention=dict(rules["full_attention"], rope_type="dynamic"))},
             "default or a yarn rule"),
            ({"rope_parameters": {"full_attention": rules["full_attention"]}}, "default or a yarn rule"),
            ({"layer_types": [S, S, S, "chunked_attention"] * 7}, "sliding_attention or full_attention"),
            ({"layer_types": [S, S]}, "sliding_attention or full_attention"),
            ({"held_expert_ids": list(range(15)) + [64]}, "held_expert_ids"),
            ({"held_expert_ids": list(range(8))}, "held_expert_ids"),
            ({"num_key_value_heads": 5}, "multiple")):
        with pytest.raises(ValueError, match=match):
            FAMILY.check(dict(sizes, **over))


def test_the_configuration_holds_every_published_number():
    """Every key of the published ``config.json`` under its own name, but for
    the three cuts, which state their published values; what it has no key
    for is under ``assumed``."""
    import json

    config = files.load_config(CONFIG)
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 7168, "layer_types": [S, S, S, F] * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
        "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True}
    entry = files.load_config_entry(config["name"])
    cut = set(entry["reduced"])
    assert cut == set(config["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    for key, value in published.items():
        assert (config["published"] if key in cut else config)[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 16, 12288)
    assert config["router_outputs"] == 64 and config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["params"] == FAMILY.n_params(model.sizes_of(config)) == 538_531_072
    assumed = config["assumed"]
    assert assumed["seq_len"] == 8192 and assumed["held_expert_ids"] == list(range(16))
    assert assumed["remat_policy"] == "full" and assumed["attn_impl"] == "flash"
    for key in ("qk_norm", "rope", "yarn_truncate", "router", "shared_expert", "dense_ffn", "aux_loss", "mtp_head",
                "unread_keys", "expert_slack_why"):
        assert assumed[key], key
    assert "Qwen3-MoE" in assumed["_why"] and "12,149,923,072" in assumed["_why"]
    assert "no key for one" in assumed["mtp_head"]
    files.check_config(config, entry["reduced"], FAMILY)
    json.dumps(config)


def test_the_compiled_step_is_released_before_the_reference_runs():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config(CONFIG)
    sizes = model.sizes_of(config, bench_tiny.of_family("mellum")["tiny"]["config"])
    weights = jax.jit(FAMILY.make_weights_fn(sizes))(model.seed_key(1))
    tokens = jnp.asarray(model.tokens_for(256, 1, 64, 1, 0, 0))
    step = FAMILY.make_grad_step(sizes, 64)
    assert step.__name__ == "step"
    compiled = step.lower(weights, tokens).compile()
    loss, _ = compiled(weights, tokens)
    text, analysis = compiled.as_text(), compiled.memory_analysis()
    stats = FAMILY.make_routing_stats(sizes)(weights, tokens)
    # eight of sixteen held, four a token: a token lands here up to four times
    assert stats["assignments"].shape == (4, 8) and 64 < int(stats["assignments"][0].sum()) <= 64 * 4
    want = FAMILY.reference_loss(weights, tokens, sizes, None)   # releases
    assert compiled._executable is None
    assert compiled.as_text() == text and compiled.memory_analysis() is analysis
    assert abs(float(loss) - float(want)) < 0.02 * abs(float(want))
    with pytest.raises(TypeError):
        compiled(weights, tokens)  # the window is over
