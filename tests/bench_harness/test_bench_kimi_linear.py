"""What the ``kimi_linear`` family brings beside the members every family has:
operation and byte counts worked by hand, the device trace read by the
program's scopes, and the compiled step that lets go of the chip's memory
before the reference runs."""

import types

import pytest

from benchmarks.harness import files, model

FAMILY = files.load_family("kimi_linear")
SMALL = {
    "linear_attn_config": {"num_heads": 2, "head_dim": 8, "kda_layers": [1, 2, 3], "full_attn_layers": [4],
                           "short_conv_kernel_size": 4},
    "kda_chunk": 4, "compute_dtype": "bfloat16", "num_attention_heads": 2, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "num_hidden_layers": 4,
}


def test_delta_rule_work_is_the_count_worked_by_hand():
    """3 rows of 10 steps, 2 heads of 8, chunks of 4: 6 row-heads x 3 chunks
    (the last one part full) x (10 x 4^2 x 8 + 6 x 4 x 8^2) operations."""
    work = FAMILY.kda_work(SMALL, 3, 10)
    assert work["forward"]["flops"] == 6 * 3 * (10 * 16 * 8 + 6 * 4 * 64) == 50688
    assert work["backward"]["flops"] == 2 * 50688
    wide, states, beta = 6 * 10 * 8, 6 * 3 * 64, 6 * 10
    # q, k, v, o in bfloat16, g and beta in float32, the states written and read
    assert work["forward"]["bytes"] == 4 * wide * 2 + wide * 4 + beta * 4 + 2 * states * 2
    # everything the forward read, do, five gradients, the states' gradients
    assert work["backward"]["bytes"] == (
        (3 * wide * 2 + wide * 4 + beta * 4 + 2 * states * 2) + 2 * wide * 2
        + (3 * wide * 2 + wide * 4 + beta * 4) + 2 * states * 2)


def test_latent_attention_work_counts_192_for_keys_and_128_for_values():
    work = FAMILY.flash_attn_work(SMALL, 3, 10)
    pairs = 3 * 2 * (10 * 11 // 2)
    assert work["_fwd_kernel"]["flops"] == 2 * pairs * (12 + 8)
    assert work["_bwd_kv_kernel"]["flops"] == 2 * pairs * (2 * 12 + 2 * 8)
    assert work["_bwd_q_kernel"]["flops"] == 2 * pairs * (2 * 12 + 8)
    wide, narrow, stat = 6 * 10 * 12 * 2, 6 * 10 * 8 * 2, 6 * 10 * 4
    assert work["_fwd_kernel"]["bytes"] == 2 * wide + 2 * narrow + stat            # q k | v o | lse
    assert work["_bwd_kv_kernel"]["bytes"] == 3 * wide + 3 * narrow + 2 * stat     # q k dk | v do dv
    assert work["_bwd_q_kernel"]["bytes"] == 3 * wide + 2 * narrow + 2 * stat      # q k dq | v do
    config = files.load_config("kimi-linear-48b-a3b-ep32")
    real = FAMILY.flash_attn_work(model.sizes_of(config), 4, 4096)
    assert set(real) == set(FAMILY.FLASH_KERNELS)


def _run(ops, runs=2):
    return {"trace": {"ops": ops, "module_seconds": {"jit_step": [1.0] * runs}}, "grad_module": "jit_step",
            "family": FAMILY, "sizes": dict(SMALL, num_hidden_layers=4), "device_kind": "TPU v5 lite",
            "traffic": {"batch_per_group": 3, "seq_len": 10}}


def _op(op_name, seconds, module="jit_step"):
    return {"module": module, "label": "fusion.1", "seconds": seconds, "calls": 2, "op_name": op_name,
            "kernel": None}


OPS = [
    _op("jit(step)/jvp(kda)/dot_general", 0.010),                                   # right under a transform
    _op("jit(step)/transpose(jvp())/checkpoint/rematted_computation/kda/while/body/mul", 0.020),
    _op("jit(step)/transpose(jvp())/checkpoint/kda.proj/checkpoint/reduce_sum", 0.100),
    _op("jit(step)/jvp()/while/body/closed_call/checkpoint/moe.route/top_k", 0.004),
    _op("jit(step)/transpose(jvp(moe.experts))/cond/branch_1_fun/checkpoint/moe.gathered/ragged_dot", 0.004),
    _op("jit(step)/jvp()/checkpoint/moe.experts/cond/branch_0_fun/checkpoint/moe.masked/while/body/mul", 0.001),
    _op("jit(step)/jvp()/checkpoint/moe.experts/cond", 0.001),
    _op("jit(step)/jvp(moe.shared)/dot_general", 0.050),
    _op("jit(other)/kda/mul", 9.0, module="jit_other"),
    _op(None, 1.0),
]


def test_rows_are_found_by_the_programs_scopes():
    rows = FAMILY.scope_rows(_run(OPS), ("kda",))
    assert [r["seconds"] for r in rows] == [0.010, 0.020]  # not kda.proj, not another program's
    assert FAMILY.scope_ms(_run(OPS), ("kda",)) == pytest.approx(15.0)
    assert FAMILY.scope_ms(_run(OPS), ("moe.route", "moe.experts")) == pytest.approx(5.0)


def test_the_readers_on_a_run():
    run = _run(OPS)
    assert files.load_layer_metric("kda_ms").read(run) == pytest.approx(15.0)
    assert files.load_layer_metric("moe_experts_ms").read(run) == pytest.approx(5.0)
    # of the two paths' 5 ms a fifth was the masked one's; the `cond` itself is neither's
    assert files.load_layer_metric("moe_masked_path_pct").read(run) == pytest.approx(20.0)
    # three KDA layers of the four run, the forward twice (a row is rematted)
    from benchmarks.harness import peaks

    work = FAMILY.kda_work(run["sizes"], 3, 10)

    def floor(part):
        return peaks.roofline_seconds("TPU v5e", work[part]["flops"], work[part]["bytes"])

    least = 3 * 2 * (2 * floor("forward") + floor("backward"))
    assert files.load_layer_metric("kda_roofline_pct").read(run) == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("name", ["kda_ms", "kda_roofline_pct", "moe_experts_ms", "moe_masked_path_pct"])
def test_a_reader_without_the_family_or_a_device_reads_nothing_or_zero(name):
    reader = files.load_layer_metric(name)
    other = types.SimpleNamespace()  # another family: no scopes to read by
    assert reader.read(dict(_run(OPS), family=other)) is None
    # a rehearsal on the CPU: the program ran, no device did
    cpu = {"trace": {"ops": [], "module_seconds": {}}, "grad_module": "jit_step", "family": FAMILY,
           "sizes": SMALL, "traffic": {"batch_per_group": 3, "seq_len": 10}, "device_kind": "cpu"}
    assert reader.read(cpu) == 0.0


def test_the_compiled_step_is_released_once_its_group_has_ended():
    import jax
    import jax.numpy as jnp

    import bench_tiny

    config = files.load_config("kimi-linear-48b-a3b-ep32")
    sizes = model.sizes_of(config, bench_tiny.of_family("kimi_linear")["tiny"]["config"])
    weights = jax.jit(FAMILY.make_weights_fn(sizes))(model.seed_key(1))
    tokens = jnp.asarray(model.tokens_for(256, 1, 64, 1, 0, 0))
    step = FAMILY.make_grad_step(sizes, 64)
    assert step.__name__ == "step"
    compiled = step.lower(weights, tokens).compile()
    loss, _ = compiled(weights, tokens)
    text, analysis = compiled.as_text(), compiled.memory_analysis()
    assert "HloModule" in text
    want = FAMILY.reference_loss(weights, tokens, sizes, None)   # releases
    assert compiled._executable is None
    assert compiled.as_text() == text and compiled.memory_analysis() is analysis
    assert abs(float(loss) - float(want)) < 0.02 * abs(float(want))
    with pytest.raises(TypeError):
        compiled(weights, tokens)  # the window is over
    # asking for weights releases the step of a group whose thread has ended,
    # and leaves alone the step of a thread that is still running (this one)
    import threading

    made = []
    group = threading.Thread(target=lambda: made.append(step.lower(weights, tokens).compile()))
    group.start()
    group.join()
    mine = step.lower(weights, tokens).compile()
    FAMILY.make_weights_fn(sizes)
    assert made[0]._executable is None and "HloModule" in made[0].as_text()
    assert mine._executable is not None
    assert float(mine(weights, tokens)[0]) == float(loss)
    FAMILY.reference_loss(weights, tokens, sizes, None)
    assert mine._executable is None
