"""What the ``sdar`` family brings beside the members every family has: the
live pairs of the three block-diffusion masks, the flash kernels' work under
them, an operation count that leaves out what the loss does not need (worked
by hand), the device trace read by the program's ``attn.diffusion``, ``head``
and ``sdar.loss`` scopes and by the kernels' own names, and a configuration
that holds every published number."""

import types

import pytest

from benchmarks.harness import files, model, peaks

FAMILY = files.load_family("sdar")
SMALL = {
    "hidden_size": 16, "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "moe_intermediate_size": 8, "num_experts": 4, "router_outputs": 8,
    "num_experts_per_tok": 2, "vocab_size": 64, "block_length": 4, "compute_dtype": "bfloat16",
    "param_dtype": "float32",
}
CLEAN = ("_fwd_block_kernel", "_bwd_kv_block_kernel", "_bwd_q_block_kernel")
BEFORE = ("_fwd_block_strict_kernel", "_bwd_kv_block_strict_kernel", "_bwd_q_block_strict_kernel")
CONFIG, CELL = "sdar-30b-a3b-ep8", "sdar-ddp1-steady"


def test_the_live_pairs_are_counted_from_the_plane():
    """A row of 12 tokens in blocks of 4, counted pair by pair from the three
    lines of the mask: 48 + 96 + 48 of the plane's 576."""
    t, block = 12, 4
    count = {"own": 0, "before": 0, "clean": 0}
    for i in range(2 * t):
        for j in range(2 * t):
            bi, bj = (i % t) // block, (j % t) // block
            if i < t and j < t and bi == bj:
                count["own"] += 1
            elif i < t and j >= t and bi > bj:
                count["before"] += 1
            elif i >= t and j >= t and bi >= bj:
                count["clean"] += 1
    assert FAMILY.live_pairs(t, block) == count == {"own": 48, "before": 48, "clean": 96}
    real = FAMILY.live_pairs(4096, 4)
    assert sum(real.values()) == 4096 * 4096 + 4096 * 4, "a quarter of the [2T, 2T] plane and 4 T more"
    assert real["own"] / sum(real.values()) < 0.001


def test_flash_work_counts_each_call_by_its_masks_live_pairs():
    """3 rows of 12 tokens, 4 heads of 8: the clean copy's call 96 pairs a
    head, the noised copy's on the clean keys 48; a skipped tile is not
    counted."""
    work = FAMILY.flash_block_work(SMALL, 3, 12)
    assert set(work) == set(CLEAN + BEFORE) == set(FAMILY.FLASH_BLOCK_KERNELS + FAMILY.FLASH_STRICT_KERNELS)
    for names, pairs in ((CLEAN, 12 * 96), (BEFORE, 12 * 48)):
        fwd, bwd_kv, bwd_q = (work[name] for name in names)
        assert fwd["flops"] == 2 * 2 * pairs * 8
        assert bwd_kv["flops"] == 2 * 4 * pairs * 8
        assert bwd_q["flops"] == 2 * 3 * pairs * 8
        tile, stat = 12 * 12 * 8 * 2, 12 * 12 * 4
        assert fwd["bytes"] == 4 * tile + stat
        assert bwd_kv["bytes"] == 6 * tile + 2 * stat
        assert bwd_q["bytes"] == 5 * tile + 2 * stat
    # at heads of 128 the operations bound every one of the six
    real = FAMILY.flash_block_work(model.sizes_of(files.load_config(CONFIG)), 4, 4096)
    for need in real.values():
        assert need["flops"] / 197e12 > need["bytes"] / 819e9
    assert real["_fwd_block_kernel"]["flops"] / real["_fwd_block_strict_kernel"]["flops"] == pytest.approx(
        1025 / 1023)


def test_model_flops_count_what_the_loss_needs():
    """Three layers: both copies meet every matrix in two, the noised copy in
    the third, whose clean copy meets k and v alone; the head runs over the
    noised copy; attention has no clean query in the last layer."""
    whole = 2 * 16 * 32 + 2 * 16 * 16 + 16 * 8 + (2 * 4 / 8) * 3 * 16 * 8
    per_token = 5 * whole + 2 * 16 * 16 + 16 * 64
    attention = 3 * 2 * 2 * 12 * 8 * (3 * (48 + 48) + 2 * 96)
    assert FAMILY.flops_per_step(SMALL, 3, 12) == 6 * per_token * 36 + attention
    sizes = model.sizes_of(files.load_config(CONFIG))
    step = FAMILY.flops_per_step(sizes, 4, 4096)
    assert step == 6 * 207_978_496 * 16384 + 11_556_146_380_800 == pytest.approx(32.00e12, rel=1e-3)
    # by operations: projections 41 %, attention 36 %, the head 12 %, routed experts 10 %, the router 1 %
    tokens = 6 * 16384
    share = {"projections": (7 * 18_874_368 + 2_097_152) * tokens / step, "head": 2048 * 18992 * tokens / step,
             "attention": 11_556_146_380_800 / step, "experts": 7 * 3 * 2048 * 768 * tokens / step}
    assert share == pytest.approx({"projections": 0.412, "head": 0.119, "attention": 0.361, "experts": 0.101},
                                  abs=2e-3)
    # a program that runs the last layer's clean copy whole does 11.8 % more than the loss needs
    wasted = 6 * (23_855_104 - 2_097_152) * 16384 + 3 * 2 * 2 * 128 * 128 * 8_396_800
    assert wasted / step == pytest.approx(0.118, abs=2e-3)
    # five layers, which ISSUE 48 asked for first and the chip's memory refused: 39.99 TFLOP
    assert FAMILY.flops_per_step(dict(sizes, num_hidden_layers=5), 4, 4096) == pytest.approx(39.99e12, rel=1e-3)


def test_the_layer_pattern_and_the_count():
    assert FAMILY.layer_pattern(SMALL) == {"leading_dense": 0, "period": 1}
    assert {"head_dim", "router_outputs", "num_experts_per_tok"} <= set(FAMILY.WIDTH_KEYS)
    assert {"block_length", "mask_token_id", "noise_seed", "t_eps", "expert_slack"} <= set(FAMILY.ASSUMED_KEYS)
    assert FAMILY.STACKED == ("attn", "moe")
    shapes = FAMILY.weight_shapes(SMALL)
    assert not any(name.startswith("shared") for name in shapes["moe"])
    assert shapes["head"] == (16, 64) and shapes["moe"]["router"] == (3, 16, 8)
    assert shapes["attn"]["wq"] == (3, 16, 32) and shapes["attn"]["wk"] == (3, 16, 16)
    sizes = model.sizes_of(files.load_config(CONFIG))
    assert FAMILY.n_params(sizes) == 456_346_624 == 4 * 94_638_336 + 2 * 38_895_616 + 2048   # the floor
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=5)) == 550_984_960      # what ISSUE 48 asked for first
    assert FAMILY.n_params(dict(sizes, num_hidden_layers=6)) == 645_623_296      # 15.5 GB in the reference
    whole = dict(sizes, num_hidden_layers=48, num_experts=128, vocab_size=151936)
    assert FAMILY.n_params(whole) == 30_532_122_624


def _run(ops, runs=2, family=FAMILY):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": family, "sizes": SMALL, "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 12}}


def _op(op_name, seconds, kernel=None, calls=2, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": calls, "op_name": op_name,
            "kernel": kernel}


OPS = [
    _op("jit(step)/jvp()/sdar.corrupt/random_bits", 0.001),
    _op("jit(step)/jvp()/checkpoint/attn.diffusion/attn.rope/mul", 0.002),
    _op("jit(step)/jvp()/checkpoint/attn.diffusion/pallas_call", 0.010, "_fwd_block_kernel", calls=4),
    _op("jit(step)/jvp()/checkpoint/attn.diffusion/pallas_call", 0.009, "_fwd_block_strict_kernel", calls=4),
    _op("jit(step)/jvp()/checkpoint/attn.diffusion/reduce_sum", 0.003),
    _op("jit(step)/transpose(jvp())/checkpoint/attn.diffusion/pallas_call", 0.012, "_bwd_q_block_strict_kernel"),
    _op("jit(step)/jvp()/checkpoint/attn.proj/dot_general", 0.300),
    _op("jit(step)/jvp()/checkpoint/moe.route/moe.route.score/top_k", 0.006),
    _op("jit(step)/jvp()/checkpoint/moe.experts/cond/branch_1_fun/checkpoint/moe.gathered/ragged_dot", 0.040),
    _op("jit(step)/jvp()/sdar.loss/while/body/checkpoint/head/dot_general", 0.020),
    _op("jit(step)/jvp()/sdar.loss/while/body/checkpoint/reduce_max", 0.004),
    _op("jit(step)/transpose(jvp())/sdar.loss/while/body/checkpoint/head/transpose/dot_general", 0.030),
    _op("jit(other)/attn.diffusion/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_the_readers_on_a_run():
    run = _run(OPS)
    # per grad step, of two: every operation under the scope, forward and backward; not another program's
    assert files.load_layer_metric("attn_diffusion_ms").read(run) == pytest.approx(1e3 * 0.036 / 2)
    # the head's products and the loss around them, counted once where the scopes nest
    assert files.load_layer_metric("diffusion_loss_ms").read(run) == pytest.approx(1e3 * 0.054 / 2)
    # the accepted readers on this family's run
    assert files.load_layer_metric("moe_route_ms").read(run) == pytest.approx(1e3 * 0.006 / 2)
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(1e3 * 0.046 / 2)
    assert files.load_layer_metric("moe_masked_path_pct").read(run) == 0.0
    work = FAMILY.flash_block_work(SMALL, 3, 12)

    def floor(name, calls):
        return calls * peaks.roofline_seconds("TPU v5e", work[name]["flops"], work[name]["bytes"])

    least = floor("_fwd_block_kernel", 4) + floor("_fwd_block_strict_kernel", 4) + floor(
        "_bwd_q_block_strict_kernel", 2)
    assert files.load_layer_metric("flash_block_roofline_pct").read(run) == pytest.approx(100 * least / 0.031)


@pytest.mark.parametrize("name", ["attn_diffusion_ms", "diffusion_loss_ms", "flash_block_roofline_pct"])
def test_a_reader_without_the_family_a_device_or_the_scope(name):
    """A family without the block-diffusion step, a run without operations and
    a program that lacks the scopes and the kernels (as the parent's) read
    nothing or zero and do not raise."""
    reader = files.load_layer_metric(name)
    assert reader.read(_run(OPS, family=types.SimpleNamespace())) is None
    assert reader.read(_run(OPS, family=files.load_family("mellum"))) is None
    assert reader.read({"records": [], "trace": {"module_seconds": {}}, "grad_module": "jit_step",
                        "family": FAMILY}) is None
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 12}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0
    assert reader.read(_run([_op("jit(step)/jvp()/attn.proj/dot_general", 0.3)])) == 0.0


def test_the_metrics_are_reported_by_the_one_cell():
    bench = files.load_benchmark_json()
    for name, unit, better, layer in (("attn_diffusion_ms", "ms", "lower", "L1 kernels"),
                                      ("flash_block_roofline_pct", "%", "higher", "L1 kernels"),
                                      ("diffusion_loss_ms", "ms", "lower", "L1 model step")):
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(entry) == 1 and CELL in entry[0]["workloads"]   # a later cell may join the list
        assert (entry[0]["unit"], entry[0]["better"], entry[0]["moves"], entry[0]["layer"], entry[0]["source"]) == (
            unit, better, "tokens_per_s", layer, "program_span")
    reports = files.reported("per_layer", CELL)
    for name in ("moe_experts_ms", "moe_masked_path_pct", "grad_step_mfu_pct", "fwdbwd_ms", "step_median_ms",
                 "attn_diffusion_ms", "flash_block_roofline_pct", "diffusion_loss_ms"):
        assert name in reports, name
    # no plain causal call remains in the step, no window layer either; ``moe_route_ms`` is left out
    # because an accepted test (test_bench_mellum.py) holds its list to five cells and no PR of this
    # kind may edit that file: ``moe_experts_ms`` holds the router's time with the experts'
    for name in ("flash_attn_roofline_pct", "flash_window_roofline_pct", "attn_local_ms", "attn_global_ms",
                 "moe_route_ms"):
        assert name not in reports, name
    assert set(files.reported("end_to_end", CELL)) == {"tokens_per_s", "setup_s"}
    cell = files.load_workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "steady-1x4x4096", 1)
    assert "tokens_per_s counts the 16384 tokens" in cell["why"]
    assert len(bench["workloads"]) >= 10 and sum(w["chips"] == 4 for w in bench["workloads"]) >= 1


def test_sizes_the_program_cannot_express_are_refused():
    sizes = model.sizes_of(files.load_config(CONFIG))
    FAMILY.check(sizes)
    for over, match in (
            ({"mlp_only_layers": [0]}, "expresses"),
            ({"decoder_sparse_step": 2}, "expresses"),
            ({"tie_word_embeddings": True}, "expresses"),
            ({"norm_topk_prob": False}, "expresses"),
            ({"rope_scaling": {"rope_type": "yarn"}}, "expresses"),
            ({"use_sliding_window": True}, "expresses"),
            ({"held_expert_ids": list(range(15)) + [128]}, "held_expert_ids"),
            ({"held_expert_ids": list(range(8))}, "held_expert_ids"),
            ({"num_key_value_heads": 5}, "multiple"),
            ({"mask_token_id": 18992}, "mask_token_id"),
            ({"mask_token_id": 151669}, "mask_token_id"),
            ({"block_length": 3}, "block_length"),
            ({"t_eps": 0.0}, "t_eps")):
        with pytest.raises(ValueError, match=match):
            FAMILY.check(dict(sizes, **over))


def test_the_configuration_holds_every_published_number():
    """Every key of the published ``config.json`` (the catalog's row) under its
    own name, but for the three cuts, which state their published values;
    what it has no key for is under ``assumed`` with its source."""
    import json

    config = files.load_config(CONFIG)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    entry = files.load_config_entry(config["name"])
    cut = set(entry["reduced"])
    assert cut == set(config["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    for key, value in published.items():
        assert (config["published"] if key in cut else config)[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 16, 18992)
    assert config["vocab_size"] * 8 == 151936
    assert config["router_outputs"] == 128 and config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["params"] == FAMILY.n_params(model.sizes_of(config)) == 456_346_624
    assumed = config["assumed"]
    assert assumed["seq_len"] == 4096 and assumed["held_expert_ids"] == list(range(16))
    assert assumed["remat_policy"] == "full" and assumed["attn_impl"] == "flash"
    assert (assumed["block_length"], assumed["mask_token_id"], assumed["t_eps"]) == (4, 18991, 0.001)
    for key in ("noise", "input", "attention_mask", "loss", "qk_norm", "rope", "router", "shared_expert",
                "dense_ffn", "aux_loss", "unread_keys", "generation", "expert_slack_why", "learning_rate_why",
                "block_length_why", "mask_token_id_why"):
        assert assumed[key], key
    for key, source in (("noise", "2502.09992"), ("input", "2503.09573"), ("attention_mask", "2503.09573"),
                        ("loss", "2502.09992"), ("block_length_why", "2510.06303")):
        assert source in assumed[key], key
    assert "no shift" in assumed["loss"] and "fold_in" in assumed["noise"]
    assert "Qwen3-MoE" in assumed["_why"] and "30,532,122,624" in assumed["_why"]
    files.check_config(config, entry["reduced"], FAMILY)
    json.dumps(config)


def test_the_compiled_step_is_released_before_the_reference_runs():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config(CONFIG)
    sizes = model.sizes_of(config, bench_tiny.of_family("sdar")["tiny"]["config"])
    weights = jax.jit(FAMILY.make_weights_fn(sizes))(model.seed_key(1))
    tokens = jnp.asarray(model.tokens_for(256, 1, 64, 1, 0, 0))
    step = FAMILY.make_grad_step(sizes, 64)
    assert step.__name__ == "step"
    compiled = step.lower(weights, tokens).compile()
    loss, _ = compiled(weights, tokens)
    text, analysis = compiled.as_text(), compiled.memory_analysis()
    stats = FAMILY.make_routing_stats(sizes)(weights, tokens)
    # 2T positions go through the router: eight of sixteen held, four a position
    assert stats["assignments"].shape == (3, 8) and 128 < int(stats["assignments"][0].sum()) <= 128 * 4
    assert 0.0 < float(stats["masked_share"]) < 1.0 and stats["p"].shape == (1,)
    want = FAMILY.reference_loss(weights, tokens, sizes, None)   # releases
    assert compiled._executable is None
    assert compiled.as_text() == text and compiled.memory_analysis() is analysis
    assert abs(float(loss) - float(want)) < 0.02 * abs(float(want))
    with pytest.raises(TypeError):
        compiled(weights, tokens)  # the window is over
