"""Schema checks: every configs/traffic/cells/layer_metrics/families file, and
every name and unit of BENCHMARK.json, so that a later PR's added file is held
to the same rules as these.  BENCHMARK.json is the one copy of which cell runs
what and reports what: the files hold no second one to compare."""

import json
import os

import pytest

from benchmarks.harness import files, model

BENCH = files.load_benchmark_json()


def _names(kind, ext=".json"):
    return sorted(f[:-len(ext)] for f in os.listdir(os.path.join(files.BENCH_DIR, kind))
                  if f.endswith(ext) and not f.startswith("_"))


@pytest.mark.parametrize("name", _names("configs"))
def test_config_file(name):
    cfg = files.load_config(name)
    entry = files.load_config_entry(name)
    assert entry["file"] == f"benchmarks/configs/{name}.json"
    assert entry["source"] == cfg["source"] and cfg["source"].startswith("https://")
    sizes = model.sizes_of(cfg)
    family = files.load_family(cfg["family"])
    family.check(sizes)
    # no width in `reduced`, every cut with its published value and the chips
    # that share a layer and above the guide's floors, every departure named,
    # what the file sets itself under `assumed`, `params` as the family counts
    files.check_config(cfg, entry["reduced"], family)


def _a_family(**over):
    """A family's side of the floors, in no family's keys: one dense layer
    leads, then a period of three kinds of layer."""
    import types

    return types.SimpleNamespace(**{
        "CUT_KEYS": {"layers": "depth", "experts": "experts_here", "vocab": "rows"},
        "WIDTH_KEYS": ("routes", "router_out"), "ASSUMED_KEYS": ("kernel",),
        "layer_pattern": lambda sizes: {"leading_dense": 1, "period": 3},
        "n_params": lambda sizes: 7, **over})


def _a_cut(**over):
    """A configuration cut to a chip's share, as the guide's section 4 has it."""
    cfg = {
        "name": "a-cut", "depth": 5, "experts_here": 8, "rows": 4096, "model_dim": 64,
        "routes": 2, "norm_eps": 1e-6, "params": 7,
        "published": {"depth": 28, "experts_here": 64, "rows": 32768},
        "deployment": {"chips_sharing_a_layer": 8, "how": "experts and vocabulary over 8 chips"},
        "departures": ["depth: a", "experts_here: b", "rows: c", "norm_eps: d"],
        "assumed": dict.fromkeys(files.GENERIC_ASSUMED + ("kernel",), 1),
    }
    cfg.update(over)
    return cfg


_CUT = ["depth", "experts_here", "rows", "norm_eps"]


def test_a_cut_at_the_floors_passes():
    files.check_config(_a_cut(), _CUT, _a_family())


@pytest.mark.parametrize("over,reduced,family,match", [
    ({"departures": ["depth: a", "model_dim: e"]}, ["depth", "model_dim"], {}, "no width is ever cut"),
    ({"departures": ["routes: e"]}, ["routes"], {}, "no width is ever cut"),
    ({"departures": ["kv_rank: e"]}, ["kv_rank"], {}, "no width is ever cut"),
    ({"departures": ["ffn_hidden: e"]}, ["ffn_hidden"], {}, "no width is ever cut"),
    ({"depth": 4}, _CUT, {}, "the floor is 4"),
    ({"depth": 5}, _CUT, {"layer_pattern": lambda sizes: {"leading_dense": 1, "period": 6}},
     "less than a whole period"),
    ({"experts_here": 7}, _CUT, {}, "7 experts held"),
    ({"rows": 32768 // 9}, _CUT, {}, "less than an eighth"),
    ({"published": {"depth": 28, "rows": 32768}}, _CUT, {}, "states its published value"),
    ({"deployment": "eight chips"}, _CUT, {}, "over how many chips"),
    ({"deployment": {"how": "eight chips"}}, _CUT, {}, "over how many chips"),
    ({"rows": 40000}, _CUT, {}, "is no cut of the published"),
    ({}, _CUT[:3], {}, "every key that differs from the source says why"),
    ({"assumed": dict.fromkeys(files.GENERIC_ASSUMED, 1)}, _CUT, {}, "must be under `assumed`"),
    ({"params": 8}, _CUT, {}, "not what the family counts"),
], ids=["a-width-by-its-ending", "a-width-the-family-names", "a-rank", "a-hidden-width",
        "three-layers-after-the-dense", "less-than-a-period", "seven-experts", "a-ninth-of-the-vocabulary",
        "no-published-value", "no-deployment-object", "no-chips-sharing-a-layer", "a-cut-that-grows",
        "a-departure-not-named", "an-assumed-key-missing", "params-miscounted"])
def test_a_configuration_below_the_floors_is_refused(over, reduced, family, match):
    with pytest.raises(ValueError, match=match):
        files.check_config(_a_cut(**over), reduced, _a_family(**family))


FAMILY_MEMBERS = ("check", "make_weights_fn", "program_init_shapes", "n_params", "make_grad_step",
                  "flops_per_step", "reference_loss", "layer_pattern")


@pytest.mark.parametrize("name", _names("families", ".py"))
def test_family_file(name):
    """What the harness takes from a family, and nothing of it by name."""
    family = files.load_family(name)
    for attr in FAMILY_MEMBERS:
        assert callable(getattr(family, attr)), attr
    assert set(family.CUT_KEYS) == {"layers", "experts", "vocab"} and family.CUT_KEYS["vocab"]
    assert all(isinstance(group, str) for group in family.STACKED)
    assert isinstance(family.WIDTH_KEYS, tuple) and isinstance(family.ASSUMED_KEYS, tuple)
    assert any(c["family"] == name for c in map(files.load_config, _names("configs")))
    # its presets for the rehearsals, in its own keys
    import bench_tiny

    tiny = bench_tiny.of_family(name)
    assert {"tiny", "wide", "float32_parity", "flops_check"} <= set(tiny)
    assert {"config", "traffic", "limits"} <= set(tiny["tiny"])


@pytest.mark.parametrize("name", _names("traffic"))
def test_traffic_file(name):
    t = files.load_traffic(name)
    assert t["algorithm"] in files.ALGORITHMS_BUILT
    assert isinstance(t["kills"], list) and "who_sends_it" in t and "reduced" in t
    for kill in t["kills"]:
        assert set(kill) == {"group", "at_measured_step"}


@pytest.mark.parametrize("algorithm", files.ALGORITHMS_RESERVED)
def test_reserved_algorithms_say_where_to_read(algorithm):
    t = dict(files.load_traffic(_names("traffic")[0]), algorithm=algorithm)
    with pytest.raises(NotImplementedError, match="PERF.md"):
        files.check_traffic(t)


def test_group_zero_is_never_killed():
    t = dict(files.load_traffic(_names("traffic")[0]), groups=2,
             kills=[{"group": 0, "at_measured_step": 1}])
    with pytest.raises(ValueError, match="group 0"):
        files.check_traffic(t)


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_cell(entry):
    name = entry["name"]
    assert files.load_workload(name) == entry
    assert files.load_config(entry["config"])["name"] in {c["name"] for c in BENCH["configs"]}
    traffic = files.load_traffic(entry["traffic"])
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert traffic["groups"] * traffic["chips_per_group"] >= entry["chips"]
    with open(os.path.join(files.BENCH_DIR, "cells", name + ".json")) as f:
        assert set(json.load(f)) == {"name", "limits", "limits_why"}, (
            "a cell file holds the limits of `correct`; the rest is BENCHMARK.json's")
    for number, limit in files.load_limits(name).items():
        assert limit >= 0, number
    end_to_end = files.reported("end_to_end", name)
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    moved = {m["moves"] for m in BENCH["per_layer"] if m["name"] in files.reported("per_layer", name)}
    assert moved and moved <= set(end_to_end), "a per-layer metric moves one this cell reports"
    if traffic["kills"]:
        assert {"recover_s", "survivor_stall_s"} <= set(end_to_end)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_layer_metric_file(entry):
    """A reader per entry, found by the entry's name; its unit, layer and
    cells are the entry's."""
    reader = files.load_layer_metric(entry["name"])
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert reader.read({"records": [], "kills": [], "trace": {"module_seconds": {}},
                        "grad_module": "jit_step"}) is None, "nothing to read, nothing returned"


def test_every_reader_has_an_entry():
    assert set(_names("layer_metrics", ".py")) == {m["name"] for m in BENCH["per_layer"]}


def _all_metrics():
    return [(kind, m) for kind in ("end_to_end", "per_layer") for m in BENCH[kind]]


@pytest.mark.parametrize("kind,metric", _all_metrics(),
                         ids=[m["name"] for _, m in _all_metrics()])
def test_benchmark_json_metric(kind, metric):
    assert files.NAME_RE.match(metric["name"])
    assert files.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        allowed |= {"bound"}
    else:
        assert metric["source"] in ("device_trace", "program_span", "program_counter",
                                    "host_clock")
        allowed |= {"layer", "moves"}
    assert set(metric) <= allowed
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"][:2] == ["python3", "benchmarks/run.py"]
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(files.CHECKOUT, path))
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert files.NAME_RE.match(entry["name"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert len(json.dumps(BENCH)) < 64 * 1024
