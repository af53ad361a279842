"""One rehearsal of the README's recipe: another family, cut to a chip's share
of a deployment, lands in the benchmark by files and entries alone.

Copies ``BENCHMARK.json`` + ``benchmarks/`` + ``tests/bench_harness/`` to a
temporary root, lays the fixture (``fixture/``: a family the benchmark does not
list, with its plain reference, a cut configuration, its tiny file, a traffic
file, a cell file, a per-layer metric) over it, adds the fixture's entries to
the copy's ``BENCHMARK.json``, and runs this directory's own tests against
that root.  No file that was there differs afterwards."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import files

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
COPIED = ("benchmarks", os.path.join("tests", "bench_harness"))


def _digests(root):
    out = {}
    for top in COPIED:
        for folder, _, names in os.walk(os.path.join(root, top)):
            for name in names:
                path = os.path.join(folder, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("recipe"))
    ignore = shutil.ignore_patterns("__pycache__", "*.pyc")
    for top in COPIED:
        shutil.copytree(os.path.join(files.CHECKOUT, top), os.path.join(root, top), ignore=ignore)
    before = _digests(root)
    bench = files.load_benchmark_json()

    added = []
    for folder, _, names in os.walk(FIXTURE):
        for name in names:
            rel = os.path.relpath(os.path.join(folder, name), FIXTURE)
            if rel == "entries.json" or "__pycache__" in rel:
                continue
            assert rel not in before, f"the fixture would edit {rel}"
            os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
            shutil.copy(os.path.join(folder, name), os.path.join(root, rel))
            added.append(rel)
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        entries = json.load(f)
    new = json.loads(json.dumps(bench))
    for kind in ("configs", "workloads", "per_layer"):
        new[kind].extend(entries[kind])
    cell = entries["workloads"][0]["name"]
    for metric in new["per_layer"]:
        if metric["name"] in entries["also_reports"]:
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f, indent=1)

    # one device and one compute thread: the copy's suite runs beside the
    # rest of tier-1 and must not starve tests that time things
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([root, files.CHECKOUT]),
               XLA_FLAGS="--xla_force_host_platform_device_count=1 --xla_cpu_multi_thread_eigen=false",
               OMP_NUM_THREADS="1")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join("tests", "bench_harness"), "-v",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         "--ignore", os.path.join("tests", "bench_harness", os.path.basename(__file__)),
         # one rehearsal of the cell is enough here: the traced one only
         # repeats, for readers of the host's clocks, what the cells that are there show
         "-k", "(fx or every_reader or json_shape or quarter) and not per_layer_metric_reported"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    return {"root": root, "before": before, "added": added, "bench": bench, "new": new,
            "proc": proc, "entries": entries}


def _passed(proc):
    return set(re.findall(r"^(\S+) PASSED", proc.stdout, flags=re.M))


def test_the_copys_tests_pass_with_the_fixture_added(rehearsed):
    proc = rehearsed["proc"]
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-2000:]
    assert " failed" not in proc.stdout.splitlines()[-1]


# each of the five repairs, by the case of the copy's suite that exercises it
@pytest.mark.parametrize("case", [
    "test_bench_files.py::test_config_file[fx-share]",                     # a cut configuration through the floors
    "test_bench_files.py::test_family_file[fx_two_stacks]",
    "test_bench_files.py::test_traffic_file[fx-steady-1x2]",
    "test_bench_files.py::test_cell[fx-steady]",
    "test_bench_files.py::test_layer_metric_file[fx_pallas_ms]",
    "test_bench_files.py::test_every_reader_has_an_entry",
    "test_bench_reference.py::test_weights_are_the_programs_layout[fx-share]",   # a layout that is no other family's
    "test_bench_reference.py::test_program_in_float32_is_the_reference[fx-share]",
    "test_bench_reference.py::test_stacks_are_read_layer_by_layer[fx-share]",    # two stacks, layer by layer
    "test_bench_cells.py::test_result_line[fx-steady]",                    # its own tiny file and limits
    "test_bench_cells.py::test_end_to_end_metric_reported[fx-steady-tokens_per_s]",
    "test_bench_trace.py::test_model_flops_match_the_parameter_count[fx_two_stacks]",
    "test_bench_trace.py::test_device_trace_reader_on_the_recorded_run[fx_pallas_ms]",  # a named operation, recorded v5e trace
])
def test_the_recipe_exercises(rehearsed, case):
    passed = _passed(rehearsed["proc"])
    assert any(node.endswith(case) for node in passed), (case, sorted(passed))


def test_no_file_that_was_there_differs(rehearsed):
    after = _digests(rehearsed["root"])
    assert {k: after[k] for k in rehearsed["before"]} == rehearsed["before"]
    assert sorted(set(after) - set(rehearsed["before"])) == sorted(rehearsed["added"])
    # BENCHMARK.json: every entry that was there is there still; the only
    # change to one is the new cell's name in the list of a metric it reports
    bench, new, cell = rehearsed["bench"], rehearsed["new"], rehearsed["entries"]["workloads"][0]["name"]
    assert {k: new[k] for k in ("command", "paths", "run_seconds")} == {
        k: bench[k] for k in ("command", "paths", "run_seconds")}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, now in zip(bench[kind], new[kind]):
            now = dict(now)
            if "workloads" in now:
                now["workloads"] = [w for w in now["workloads"] if w != cell]
            assert now == old
