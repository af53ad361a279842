"""The trace -> metrics reduction on the small trace recorded on a TPU v5e
(``benchmarks/harness/testdata/tiny_v5e.xplane.pb``: three runs of a jitted
``bench_grad_step`` under ``bench.step`` / ``fwdbwd`` / ``ring`` / ``h2d`` spans),
and the interval arithmetic it rests on."""

import os

import pytest

from benchmarks.harness import files, peaks, stats, trace

RECORDED = os.path.join(os.path.dirname(trace.__file__), "testdata", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED, "bench.")


def test_recorded_trace_has_one_chip_and_the_spans(recorded):
    assert sorted(recorded["chips"]) == [0]
    assert len(recorded["chips"][0]["modules"]) == 3
    assert len(recorded["chips"][0]["ops"]) == 12
    names = [s["name"] for s in recorded["spans"]]
    assert names.count("step") == 3 and names.count("ring") == 3
    assert {s["stats"].get("step") for s in recorded["spans"] if s["name"] == "step"} == {0, 1, 2}


def test_reduction_of_the_recorded_trace(recorded):
    # the recorded spans carry no group: every span belongs to the one chip
    for s in recorded["spans"]:
        s["stats"]["group"] = 0
    out = trace.reduce(recorded, [0], {0: [0]})
    assert 0.04 < out["window_s"] < 0.06
    # twelve device operations of a microsecond or two each
    assert 0 < out["busy_s"] < 1e-4
    assert out["busy_s"] == pytest.approx(out["busy_by_chip"][0])
    # the first run started before the first span opened (the device and the
    # host clock differ by under a millisecond): two runs lie inside the window
    runs = out["module_seconds"]["jit_bench_grad_step"]
    assert len(runs) == 2 and all(1e-6 < r < 1e-5 for r in runs)
    idle = dict(out["breakdown"]["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert max(idle, key=idle.get) == "ring"
    assert len(out["breakdown"]["device_ops"]) == 4
    assert out["breakdown"]["device_ops"][0][0] in {"copy-done", "tanh_reduce_fusion", "fusion"}


@pytest.mark.parametrize("intervals,expected", [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(2, 3), (0, 1), (1, 2)], [(0, 3)]),
    ([], []),
])
def test_union(intervals, expected):
    assert trace.union(intervals) == expected


def test_gaps_and_clip():
    busy = trace.union(trace.clip([(0, 2), (5, 9)], 1, 8))
    assert busy == [(1, 2), (5, 8)]
    assert trace.gaps(busy, 1, 8) == [(2, 5)]
    assert trace.gaps([], 0, 4) == [(0, 4)]
    assert trace.total(busy) == 4


def test_gaps_are_named_by_the_inner_spans_open_then():
    spans = [
        {"name": "step", "start": 0.0, "end": 10.0},
        {"name": "fwdbwd", "start": 0.0, "end": 2.0},
        {"name": "ring", "start": 2.0, "end": 8.0},
        {"name": "ring", "start": 4.0, "end": 9.0},
    ]
    named = trace.name_gaps([(1.0, 9.5), (9.75, 11.0)], spans, ("step",))
    assert named == pytest.approx({"fwdbwd": 1.0, "ring": 7.0, "step": 0.75, "(no span)": 1.0})


def test_nested_operations_count_their_own_time_only():
    ops = [("%while.1 = x", 0.0, 10.0), ("%fusion.2 = y", 1.0, 4.0), ("%fusion.2 = y", 5.0, 6.0),
           ("%call.3 = z", 6.0, 9.0), ("%fusion.4 = w", 6.5, 8.5), ("%copy.5 = v", 11.0, 12.0)]
    assert trace.self_seconds(ops) == pytest.approx(
        {"while.1": 3.0, "fusion.2": 4.0, "call.3": 1.0, "fusion.4": 2.0, "copy.5": 1.0})


@pytest.mark.parametrize("name,label", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion.12"),
    ("%copy-done = bf16[2] copy-done(%copy-start)", "copy-done"),
    ("plain", "plain"),
])
def test_op_label(name, label):
    assert trace.op_label(name) == label


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published bf16 peak"):
        peaks.peak_flops("cpu")


def test_model_flops_match_the_parameter_count():
    sizes = dict(hidden_size=960, intermediate_size=2560, num_hidden_layers=32, head_dim=64,
                 num_attention_heads=15, num_key_value_heads=5, vocab_size=49152)
    flops = files.load_family("llama_dense").flops_per_step(sizes, 8, 2048)
    matmul_params = 361821120 - 960 * (2 * 32 + 1)  # the norms are no matmuls
    attention = 3 * 4 * 8 * 2048 * 2048 * 960 * 32
    assert flops == 6 * matmul_params * 8 * 2048 + attention


@pytest.mark.parametrize("values,q,expected", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9, 9),
    ([5, 1], 0.9, 5),
    ([3], 0.9, 3),
    (list(range(1, 21)), 0.9, 18),
])
def test_nearest_rank(values, q, expected):
    assert stats.nearest_rank(values, q) == expected


def _rec(group, step, t0, t1, **kw):
    return dict({"group": group, "step": step, "step_after": step + 1, "t_start": t0,
                 "t_end": t1, "measured": True, "committed": True, "participating": True,
                 "healed": False}, **kw)


# the kill lands a hair before, at, or a hair after the survivor's step boundary
@pytest.mark.parametrize("t_kill", [3.999, 4.0, 4.001])
def test_end_to_end_over_a_kill(t_kill):
    # group 0 survives; group 1 is killed at the start of its step 2 and heals
    # in a step (its counter still 0 when it starts) that commits step 3 at t=9
    records = [_rec(0, s, 2.0 * s, 2.0 * s + 2.0) for s in range(3)]
    records += [_rec(1, s, 2.0 * s, 2.0 * s + 1.5) for s in range(2)]
    records += [_rec(0, 3, 6.0, 9.0), _rec(0, 4, 9.0, 11.0), _rec(0, 5, 11.0, 13.5)]
    records += [_rec(1, 0, 4.5, 9.0, step_after=4, participating=False, healed=True),
                _rec(1, 4, 9.0, 11.0), _rec(1, 5, 11.0, 13.0)]
    kills = [{"group": 1, "t_kill": t_kill, "t_recovered": 9.0, "step": 2}]
    metrics, counts = stats.end_to_end(records, kills, 100, setup_s=7.0)
    assert counts["attempted"] == 11 and counts["failed"] == 0
    assert counts["group_steps_trained"] == 10  # the healing step trains on nothing
    assert metrics["tokens_per_s"] == pytest.approx(1000 / 13.5)
    assert metrics["recover_s"] == pytest.approx(9.0 - t_kill)
    assert metrics["survivor_stall_s"] == 3.0
    # steps 2 and 3 are the recovery by their numbers, wherever the clock put
    # the kill; of 0, 1, 4, 5 the p90 is the slowest
    assert counts["recovery_steps"] == [2, 3]
    assert counts["step_time_samples"] == 4
    assert metrics["step_p90_ms"] == 2500.0
    assert metrics["setup_s"] == 7.0


def test_a_kill_that_never_recovered_is_an_error():
    records = [_rec(0, 0, 0.0, 1.0)]
    kills = [{"group": 1, "t_kill": 0.5, "t_recovered": None, "step": 0}]
    with pytest.raises(RuntimeError, match="not recovered"):
        stats.end_to_end(records, kills, 1, 1.0)
