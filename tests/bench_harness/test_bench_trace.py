"""The trace -> metrics reduction on the small trace recorded on a TPU v5e
(``benchmarks/harness/testdata/tiny_v5e.xplane.pb``: three runs of a jitted
``bench_grad_step`` under ``bench.step`` / ``fwdbwd`` / ``ring`` / ``h2d`` spans),
and the interval arithmetic it rests on."""

import json
import os

import pytest

from benchmarks.harness import files, model, peaks, stats, trace

RECORDED = os.path.join(os.path.dirname(trace.__file__), "testdata", "tiny_v5e.xplane.pb")
# one traced run of a cell through `run_cell` on a v5e at sizes small enough to
# keep, with the program's Pallas kernels in the grad step, and what that run
# printed (recordings/record_flash_trace.py)
RECORDED_RUN = os.path.join(files.BENCH_DIR, "recordings", "flash_v5e")
BENCH = files.load_benchmark_json()


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
    # every device operation of the window, by the program it ran in: their
    # own seconds are the busy time, and nothing names them without an HLO
    assert sum(op["seconds"] for op in out["ops"]) == pytest.approx(out["busy_s"])
    assert {op["module"] for op in out["ops"]} == {"jit_bench_grad_step"}
    assert sum(op["calls"] for op in out["ops"]) == 8  # four operations a run, two runs inside
    assert all(op["op_name"] is None and op["kernel"] is None for op in out["ops"])
    named = trace.reduce(recorded, [0], {0: [0]}, names={"jit_bench_grad_step": {
        "copy-done": {"op_name": "jit(bench_grad_step)/copy", "kernel": None}}})
    assert [op["op_name"] for op in named["ops"] if op["label"] == "copy-done"] == [
        "jit(bench_grad_step)/copy"]
    assert any(k.startswith("copy-done bench_grad_step") or k == "copy-done jit(bench_grad_step)/copy"
               for k, _ in named["breakdown"]["device_ops"])


@pytest.fixture(scope="module")
def recorded_run():
    """The ``run`` a reader is handed, rebuilt from the recording as
    ``harness/cell.py`` builds it from a chip run's own trace."""
    with open(RECORDED_RUN + ".json") as f:
        meta = json.load(f)
    config = files.load_config(meta["config"])
    family = files.load_family(config["family"])
    sizes = model.sizes_of(config, meta["sizes_over"])
    traffic = meta["traffic"]
    reduced = trace.reduce(trace.load(RECORDED_RUN + ".xplane.pb", "bench."), [0], {0: [0]},
                           names=meta["names"])
    return meta, {
        "records": [], "kills": [], "trace": reduced, "sizes": sizes, "traffic": traffic,
        "device_kind": meta["device"]["kind"], "family": family, "grad_module": meta["grad_module"],
        "flops_per_group_step": family.flops_per_step(
            sizes, traffic["batch_per_group"], traffic["seq_len"])}


def test_operations_of_the_recorded_run_sum_to_its_busy_time(recorded_run):
    meta, run = recorded_run
    reduced = run["trace"]
    assert meta["device"]["platform"] == "tpu" and "v5" in meta["device"]["kind"]
    assert reduced["busy_s"] == pytest.approx(meta["trace"]["busy_s"], rel=1e-9)
    assert sum(op["seconds"] for op in reduced["ops"]) == pytest.approx(reduced["busy_s"], rel=0.01)
    # the grad step's operations carry the program's names; other programs' do not
    grad = [op for op in reduced["ops"] if op["module"] == run["grad_module"]]
    assert grad and sum(1 for op in grad if op["op_name"]) > 0.5 * len(grad)
    assert all(op["op_name"] is None for op in reduced["ops"] if op["module"] != run["grad_module"])


def test_kernels_of_the_recorded_run_are_found_by_the_programs_name(recorded_run):
    _, run = recorded_run
    runs = len(run["trace"]["module_seconds"][run["grad_module"]])
    calls = {}
    for op in run["trace"]["ops"]:
        if op["module"] == run["grad_module"] and op["kernel"]:
            calls[op["kernel"]] = calls.get(op["kernel"], 0) + op["calls"]
    traffic = run["traffic"]
    work = run["family"].flash_attn_work(run["sizes"], traffic["batch_per_group"], traffic["seq_len"])
    assert set(calls) == set(work)
    # one call a layer and grad step, the forward once more under remat
    layers = run["sizes"][run["family"].CUT_KEYS["layers"]]
    assert {k: v / runs / layers for k, v in calls.items()} == pytest.approx(
        {"_fwd_kernel": 2, "_bwd_kv_kernel": 1, "_bwd_q_kernel": 1})


DEVICE_READERS = [m for m in BENCH["per_layer"] if m["source"] == "device_trace"]


@pytest.mark.parametrize("entry", DEVICE_READERS, ids=[m["name"] for m in DEVICE_READERS])
def test_device_trace_reader_on_the_recorded_run(recorded_run, entry):
    """Every reader of the device trace finds its number in the recording,
    and the number the chip printed where it printed one."""
    meta, run = recorded_run
    value = files.load_layer_metric(entry["name"]).read(run)
    assert value is not None and value > 0
    if entry["name"] in meta["metrics"]:
        assert value == pytest.approx(meta["metrics"][entry["name"]], rel=1e-9)
    if entry["unit"] == "%":
        assert value < 100


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
    assert trace.self_times(ops) == pytest.approx(  # [own seconds, events]
        {"while.1": [3.0, 1], "fusion.2": [4.0, 2], "call.3": [1.0, 1], "fusion.4": [2.0, 1],
         "copy.5": [1.0, 1]})


@pytest.mark.parametrize("name,label", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion.12"),
    ("%copy-done = bf16[2] copy-done(%copy-start)", "copy-done"),
    ("plain", "plain"),
])
def test_op_label(name, label):
    assert trace.op_label(name) == label


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    assert peaks.peak_hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(ValueError, match="no published bf16 peak"):
        peaks.peak_flops("cpu")
    with pytest.raises(ValueError, match="no published bf16 peak"):
        peaks.roofline_seconds("cpu", 1.0, 1.0)


@pytest.mark.parametrize("flops,nbytes,expected", [
    (197e12, 1.0, 1.0),       # bound by the operations
    (1.0, 819e9 * 2, 2.0),    # bound by the bytes
    (197e12, 819e9, 1.0),     # at the ridge
])
def test_roofline_is_the_larger_bound(flops, nbytes, expected):
    assert peaks.roofline_seconds("TPU v5e", flops, nbytes) == pytest.approx(expected)


FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(files.BENCH_DIR, "families"))
                  if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("family", FAMILIES)
def test_model_flops_match_the_parameter_count(family):
    """The family's operation count against the one worked by hand in its
    tiny file (``flops_check``)."""
    import bench_tiny

    check = bench_tiny.of_family(family)["flops_check"]
    flops = files.load_family(family).flops_per_step(check["sizes"], check["batch"], check["seq"])
    assert flops == check["flops"]


HLO = """
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jit(main)/mlp/mul" stack_frame_id=4}
}

ENTRY %main.5 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.12 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jit(main)/mlp/mul" stack_frame_id=4}
  %copy-done = f32[8]{0} copy-done(%copy-start)
  ROOT %checkpoint.20 = f32[8]{0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"bm90IGEgbW9kdWxl","needs_layout_passes":true}}, metadata={op_name="jit(step)/transpose(jvp())/checkpoint/pallas_call" stack_frame_id=15}
}
"""


def test_hlo_names_are_the_programs_names_by_instruction():
    names = trace.hlo_names(HLO)
    assert names["fusion.12"] == {"op_name": "jit(step)/jit(main)/mlp/mul", "kernel": None}
    assert names["multiply.3"]["op_name"] == "jit(step)/jit(main)/mlp/mul"
    assert "copy-done" not in names, "an instruction the program gave no name"
    # a body this jax cannot read as a Mosaic module names no kernel
    assert names["checkpoint.20"] == {
        "op_name": "jit(step)/transpose(jvp())/checkpoint/pallas_call", "kernel": None}
    # the labels are the device events' own
    assert trace.op_label("%checkpoint.20 = f32[8]{0} custom-call(%fusion.12)") in names


def test_a_pallas_kernel_is_named_by_its_mosaic_module():
    """The serialized Mosaic module of a TPU custom call carries the kernel's
    name as its ``sym_name``; here one is built and serialized by hand."""
    import base64

    from jax.extend.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx, ir.Location.unknown():
        module = ir.Module.create()
        module.operation.attributes["sym_name"] = ir.StringAttr.get("_my_kernel")
    body = base64.b64encode(module.operation.get_asm(binary=False).encode()).decode()
    line = ('  %custom-call.7 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", '
            'backend_config={"custom_call_config":{"body":"' + body + '"}}')
    assert trace.hlo_names(line)["custom-call.7"] == {"op_name": None, "kernel": "_my_kernel"}


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


def _records_over_a_kill():
    # group 0 survives; group 1 is killed at the start of its step 2 and heals
    # in a step (its counter still 0 when it starts) that commits step 3 at t=9
    records = [_rec(0, s, 2.0 * s, 2.0 * s + 2.0) for s in range(3)]
    records += [_rec(1, s, 2.0 * s, 2.0 * s + 1.5) for s in range(2)]
    records += [_rec(0, 3, 6.0, 9.0), _rec(0, 4, 9.0, 11.0), _rec(0, 5, 11.0, 13.5)]
    records += [_rec(1, 0, 4.5, 9.0, step_after=4, participating=False, healed=True),
                _rec(1, 4, 9.0, 11.0), _rec(1, 5, 11.0, 13.0)]
    return records


# the kill lands a hair before, at, or a hair after the survivor's step boundary
@pytest.mark.parametrize("t_kill", [3.999, 4.0, 4.001])
def test_end_to_end_over_a_kill(t_kill):
    records = _records_over_a_kill()
    kills = [{"group": 1, "t_kill": t_kill, "t_recovered": 9.0, "step": 2}]
    metrics, counts = stats.end_to_end(records, kills, 100, setup_s=7.0, seconds=13.5)
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
        stats.end_to_end(records, kills, 1, 1.0, 1.0)


# the window closes inside the last steps: before, at and after a step boundary
@pytest.mark.parametrize("seconds,steps_inside", [
    (12.0, 3 + 2 + 3 + 0.4 + 0.5),     # steps 5 in flight: 1.0 of 2.5 s, 1.0 of 2.0 s
    (11.0, 3 + 2 + 3),                 # at the boundary: the steps in flight have not begun
    (10.0, 3 + 2 + 1 + 0.5 + 0.5),     # steps 4 in flight: 1.0 of 2.0 s each
    (13.5, 10), (99.0, 10),            # the loop ran no further: its last step closes the window
    (5.0, 3 + 2 + 1),                  # inside the recovery: waits for its end (9.0), as step 3 of group 0 does
])
def test_tokens_of_the_step_in_flight_count_pro_rata(seconds, steps_inside):
    records = _records_over_a_kill()
    kills = [{"group": 1, "t_kill": 4.0, "t_recovered": 9.0, "step": 2}]
    metrics, counts = stats.end_to_end(records, kills, 100, setup_s=7.0, seconds=seconds)
    close = min(13.5, max(seconds, 9.0))
    assert counts["window_s"] == pytest.approx(close)
    assert metrics["tokens_per_s"] == pytest.approx(100 * steps_inside / close)
    assert counts["tokens_per_s_to_the_last_step"] == pytest.approx(1000 / 13.5)
