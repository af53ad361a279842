"""What the ``afmoe`` family brings beside the members every family has: the
flash kernels' operations and bytes by the band (worked by hand), the device
trace read by the program's attention scopes and by the windowed kernels'
names, and the compiled step that lets go of the chip's memory before the
reference runs."""

import types

import pytest

from benchmarks.harness import files, model, peaks

FAMILY = files.load_family("afmoe")
S, F = "sliding_attention", "full_attention"
SMALL = {
    "hidden_size": 16, "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "sliding_window": 4, "layer_types": [S, S, S, F, S, S, S, F], "num_hidden_layers": 6,
    "num_dense_layers": 2, "intermediate_size": 32, "moe_intermediate_size": 8, "num_experts": 2,
    "router_outputs": 8, "num_experts_per_tok": 2, "vocab_size": 64, "compute_dtype": "bfloat16",
}
WINDOW = ("_fwd_window_kernel", "_bwd_kv_window_kernel", "_bwd_q_window_kernel")
GLOBAL = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")


@pytest.mark.parametrize("seq,window", [(10, 4), (10, 1), (10, 10), (10, 25), (8192, 2048)])
def test_the_bands_pairs_are_the_pairs_a_window_leaves(seq, window):
    """Query ``i`` sees ``min(i + 1, window)`` keys: counted one by one."""
    assert FAMILY._pairs(seq, window) == sum(min(i + 1, window) for i in range(seq))
    assert FAMILY._pairs(seq) == seq * (seq + 1) // 2 >= FAMILY._pairs(seq, window)


def test_flash_work_counts_a_window_layer_by_its_band():
    """3 rows of 10 positions, 4 heads of 8, a window of 4: a head's band is
    1 + 2 + 3 + 7 x 4 = 34 pairs where the causal half is 55."""
    work = FAMILY.flash_attn_work(SMALL, 3, 10)
    assert set(work) == set(WINDOW + GLOBAL) == set(FAMILY.FLASH_KERNELS + FAMILY.FLASH_WINDOW_KERNELS)
    for names, pairs in ((GLOBAL, 12 * 55), (WINDOW, 12 * 34)):
        fwd, bwd_kv, bwd_q = (work[name] for name in names)
        assert fwd["flops"] == 2 * 2 * pairs * 8           # S = Q K^T, P V
        assert bwd_kv["flops"] == 2 * 4 * pairs * 8        # S again, dV, dP, dK
        assert bwd_q["flops"] == 2 * 3 * pairs * 8         # S again, dP, dQ
        tile, stat = 12 * 10 * 8 * 2, 12 * 10 * 4
        assert fwd["bytes"] == 4 * tile + stat             # q k v o | lse
        assert bwd_kv["bytes"] == 6 * tile + 2 * stat      # q k v do dk dv | lse delta
        assert bwd_q["bytes"] == 5 * tile + 2 * stat       # q k v do dq | lse delta
    # a window no shorter than the sequence leaves the causal half
    wide = FAMILY.flash_attn_work(dict(SMALL, sliding_window=10), 3, 10)
    assert all(wide[w] == wide[g] for w, g in zip(WINDOW, GLOBAL))
    # the cell: a window layer does 44 % of a global layer's pairs
    config = files.load_config("trinity-mini-26b-a3b-ep16")
    real = FAMILY.flash_attn_work(model.sizes_of(config), 2, 8192)
    assert real["_fwd_window_kernel"]["flops"] / real["_fwd_kernel"]["flops"] == pytest.approx(0.4375, abs=2e-4)
    # at heads of 128 the operations bound every one of the six
    for need in real.values():
        assert need["flops"] / 197e12 > need["bytes"] / 819e9


def test_model_flops_count_a_window_layers_pairs_inside_the_band_only():
    """Five window layers and one global: the count falls by what the window
    skips, and by nothing else."""
    with_window = FAMILY.flops_per_step(SMALL, 3, 10)
    without = FAMILY.flops_per_step(dict(SMALL, sliding_window=10), 3, 10)
    skipped = 5 * 3 * (2 * 2 * 12 * (55 - 34) * 8)   # layers x (forward + backward) x products
    assert without - with_window == skipped
    per_token = (6 * (3 * 16 * 32 + 2 * 16 * 16) + 2 * 3 * 16 * 32
                 + 4 * (16 * 8 + (1 + 2 * 2 / 8) * 3 * 16 * 8) + 16 * 64)
    assert with_window == 6 * per_token * 30 + 3 * 2 * 2 * 12 * 8 * (55 + 5 * 34)


def test_the_layer_pattern_is_the_published_ratio():
    assert FAMILY.layer_pattern(dict(SMALL, global_attn_every_n_layers=4)) == {"leading_dense": 2, "period": 4}
    assert FAMILY._layers(SMALL) == ["local", "local", "local", "global", "local", "local"]


def _run(ops, runs=2):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": FAMILY, "sizes": SMALL, "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 10}}


def _op(op_name, seconds, kernel=None, calls=2, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": calls, "op_name": op_name,
            "kernel": kernel}


OPS = [
    _op("jit(step)/jvp()/checkpoint/attn.local/pallas_call", 0.010, "_fwd_window_kernel", calls=20),
    _op("jit(step)/transpose(jvp())/checkpoint/rematted_computation/attn.local/mul", 0.006),
    _op("jit(step)/transpose(jvp())/checkpoint/attn.local/pallas_call", 0.020, "_bwd_kv_window_kernel", calls=10),
    _op("jit(step)/jvp()/checkpoint/attn.global/pallas_call", 0.008, "_fwd_kernel", calls=4),
    _op("jit(step)/transpose(jvp())/checkpoint/attn.global/pallas_call", 0.012, "_bwd_q_kernel"),
    _op("jit(step)/jvp()/checkpoint/attn.proj/dot_general", 0.300),
    _op("jit(step)/jvp()/checkpoint/moe.experts/cond/branch_1_fun/checkpoint/moe.gathered/ragged_dot", 0.004),
    _op("jit(other)/attn.local/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_the_readers_on_a_run():
    run = _run(OPS)
    # per grad step, of two: the scopes, not the projections, not another program's
    assert files.load_layer_metric("attn_local_ms").read(run) == pytest.approx(18.0)
    assert files.load_layer_metric("attn_global_ms").read(run) == pytest.approx(10.0)
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(2.0)
    work = FAMILY.flash_attn_work(SMALL, 3, 10)

    def floor(name, calls):
        return calls * peaks.roofline_seconds("TPU v5e", work[name]["flops"], work[name]["bytes"])

    windowed = floor("_fwd_window_kernel", 20) + floor("_bwd_kv_window_kernel", 10)
    assert files.load_layer_metric("flash_window_roofline_pct").read(run) == pytest.approx(
        100 * windowed / 0.030)
    # the accepted share reads all six names through the same function
    every = windowed + floor("_fwd_kernel", 4) + floor("_bwd_q_kernel", 2)
    assert files.load_layer_metric("flash_attn_roofline_pct").read(run) == pytest.approx(
        100 * every / 0.050)


@pytest.mark.parametrize("name", ["attn_local_ms", "attn_global_ms", "flash_window_roofline_pct"])
def test_a_reader_without_the_family_or_a_device_reads_nothing_or_zero(name):
    reader = files.load_layer_metric(name)
    other = types.SimpleNamespace(scope_ms=FAMILY.scope_ms)  # a family without window layers
    assert reader.read(dict(_run(OPS), family=other)) is None
    assert reader.read(dict(_run(OPS), family=files.load_family("kimi_linear"))) is None
    # a rehearsal on the CPU: the program ran, no device did
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 10}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0


def test_sizes_the_program_cannot_express_are_refused():
    config = files.load_config("trinity-mini-26b-a3b-ep16")
    sizes = model.sizes_of(config)
    FAMILY.check(sizes)
    for over, match in (
            ({"score_func": "softmax"}, "expresses"),
            ({"layer_types": [S] * 32}, "global layer every"),
            ({"layer_types": [S, S, S, "chunked_attention"] * 8}, "sliding_attention or full_attention"),
            ({"held_expert_ids": [0, 1, 2, 3, 4, 5, 6, 200]}, "held_expert_ids"),
            ({"num_key_value_heads": 5}, "multiple")):
        with pytest.raises(ValueError, match=match):
            FAMILY.check(dict(sizes, **over))


def test_the_compiled_step_is_released_before_the_reference_runs():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config("trinity-mini-26b-a3b-ep16")
    sizes = model.sizes_of(config, bench_tiny.of_family("afmoe")["tiny"]["config"])
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
