"""Every cell file end to end through the harness's run function, on the CPU
at the tiny preset with a second-long window: the result's keys, every metric
the cell declares under its declared unit, and the broken paths that must
turn ``correct`` false.  CPU numbers are rehearsal numbers; none is asserted
on and none is a chip number."""

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import WINDOW_S, preset, rehearsal
from benchmarks.harness import files, loop

BENCH = files.load_benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
# read from the device trace, which a CPU run does not have
DEVICE_ONLY = {m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"}


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(cell):
    result = rehearsal(cell, False)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True
    # each number compared beside its limit, under the line's last key
    limits = {**files.load_limits(cell), **preset(cell)["limits"]}
    assert {k: v["limit"] for k, v in result["compared"].items()} == limits
    assert all(v["ok"] and v["value"] <= v["limit"] for v in result["compared"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    json.dumps(result)


def _declared(kind):
    """What ``BENCHMARK.json`` says each cell reports: the only copy."""
    return [(cell, name) for cell in CELLS for name in files.reported(kind, cell)]


@pytest.mark.parametrize("cell,metric", _declared("end_to_end"))
def test_end_to_end_metric_reported(cell, metric):
    got = rehearsal(cell, False)["metrics"]
    assert set(got) == set(files.reported("end_to_end", cell))
    assert got[metric]["unit"] == files.reported("end_to_end", cell)[metric]
    assert got[metric]["value"] > 0


@pytest.mark.parametrize("cell,metric", _declared("per_layer"))
def test_per_layer_metric_reported(cell, metric):
    result = rehearsal(cell, True)
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    got = result["metrics"]
    assert set(got) <= set(files.reported("per_layer", cell))
    if metric in DEVICE_ONLY:
        assert metric not in got, "no device trace on a CPU: the reader returns nothing"
    else:
        assert got[metric]["unit"] == files.reported("per_layer", cell)[metric]
        assert got[metric]["value"] >= 0


def test_a_new_metric_is_an_entry_and_a_reader(monkeypatch):
    """What a later PR does: one more entry in ``per_layer`` that lists a cell
    that is already there, one more reader found by that name.  No cell file,
    no harness file changes."""
    import copy
    import types

    from benchmarks.harness.cell import run_cell

    bench = copy.deepcopy(BENCH)
    bench["per_layer"].append({
        "name": "check_ms", "unit": "ms", "better": "lower", "layer": "L5 loop adapters",
        "source": "host_clock", "moves": "tokens_per_s", "workloads": [CELLS[0]]})
    reader = types.SimpleNamespace(
        read=lambda run: 1e3 * max(r["check_s"] for r in run["records"]))
    found = files.load_layer_metric
    monkeypatch.setattr(files, "load_benchmark_json", lambda: bench)
    monkeypatch.setattr(files, "load_layer_metric",
                        lambda name: reader if name == "check_ms" else found(name))
    got = run_cell(CELLS[0], 8, WINDOW_S, True, platform="cpu", preset=preset(CELLS[0]))["metrics"]
    assert got["check_ms"]["unit"] == "ms" and got["check_ms"]["value"] > 0


def test_memory_peak_names_its_parts():
    device = rehearsal(CELLS[0], False)["device"]
    assert device["memory_peak_bytes"] == max(
        device["runtime_peak_bytes_in_use"], device["grad_step_peak_bytes"])


def _flip_one_bit(state):
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten(state["params"])
    leaf = leaves[-1]
    bits = jax.lax.bitcast_convert_type(leaf, jnp.uint32).ravel().at[0].add(1).reshape(leaf.shape)
    leaves[-1] = jax.device_put(jax.lax.bitcast_convert_type(bits, leaf.dtype), leaf.sharding)
    state["params"] = jax.tree_util.tree_unflatten(tree, leaves)


def test_correct_false_when_healed_state_differs_by_one_bit(monkeypatch):
    from benchmarks.harness.cell import run_cell

    cell = next(w["name"] for w in BENCH["workloads"] if files.load_traffic(w["traffic"])["kills"])
    monkeypatch.setattr(loop, "after_heal", _flip_one_bit)
    result = run_cell(cell, 5, WINDOW_S, False, platform="cpu", preset=preset(cell))
    assert result["correct"] is False


def test_correct_false_when_the_step_returns_its_state_unchanged(monkeypatch):
    import torchft_tpu as ft
    from benchmarks.harness.cell import run_cell

    monkeypatch.setattr(ft.Optimizer, "update",
                        lambda self, params, grads, opt_state: (params, opt_state))
    result = run_cell(CELLS[0], 6, WINDOW_S, False, platform="cpu", preset=preset(CELLS[0]))
    assert result["correct"] is False


def test_run_cell_refuses_the_wrong_platform():
    from benchmarks.harness.cell import Refused, run_cell

    with pytest.raises(Refused, match="platform"):
        run_cell(CELLS[0], 1, WINDOW_S, False, preset=preset(CELLS[0]))  # expects a tpu


def test_command_line_refuses_a_cpu():
    """No TPU: non-zero exit and no result object on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=300, cwd=files.CHECKOUT)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "refused" in proc.stderr
