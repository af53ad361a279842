"""Finds a cell, a configuration with its family, a traffic mix and the
per-layer metric readers by name.  ``BENCHMARK.json`` is the one place that
says which cell runs which configuration and traffic on how many chips, and
which cell reports which metric under which unit; the files under
``benchmarks/`` hold what it has no key for.  Nothing here knows a name: a
later PR adds files and entries and edits no file that is there."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

ALGORITHMS_BUILT = ("ddp",)
ALGORITHMS_RESERVED = ("diloco", "local_sgd")


def _load(kind: str, name: str) -> Dict[str, Any]:
    if not NAME_RE.match(name):
        raise ValueError(f"{name!r} is not a valid {kind} name")
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as f:
        data = json.load(f)
    if data.get("name") != name:
        raise ValueError(f"{path} names itself {data.get('name')!r}")
    return data


def load_config(name: str) -> Dict[str, Any]:
    return _load("configs", name)


def load_traffic(name: str) -> Dict[str, Any]:
    traffic = _load("traffic", name)
    check_traffic(traffic)
    return traffic


def load_limits(cell: str) -> Dict[str, float]:
    """The limits of ``correct`` for a cell (``cells/<cell>.json``)."""
    return _load("cells", cell)["limits"]


def load_benchmark_json() -> Dict[str, Any]:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_workload(name: str) -> Dict[str, Any]:
    """The cell's entry in ``BENCHMARK.json``: config, traffic, chips, why."""
    found = [w for w in load_benchmark_json()["workloads"] if w["name"] == name]
    if len(found) != 1:
        raise FileNotFoundError(f"BENCHMARK.json has no workload {name!r}")
    return found[0]


def reported(kind: str, cell: str) -> Dict[str, str]:
    """``{metric: unit}`` of the ``end_to_end`` or ``per_layer`` metrics this
    cell reports: those that list it under ``workloads``, or list nothing."""
    return {m["name"]: m["unit"] for m in load_benchmark_json()[kind]
            if cell in m.get("workloads", [cell])}


def _module(kind: str, name: str) -> Any:
    if not NAME_RE.match(name):
        raise ValueError(f"{name!r} is not a valid name under {kind}/")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str) -> Any:
    """``families/<family>.py``, named by the configuration file: weights,
    the program's grad step, the FLOP count, the reference's loss."""
    return _module("families", name)


def load_layer_metric(name: str) -> Any:
    """``layer_metrics/<name>.py``: a module with ``read(run) -> float |
    None``.  Unit, layer and the rest are its entry in ``BENCHMARK.json``."""
    return _module("layer_metrics", name)


def check_traffic(t: Dict[str, Any]) -> None:
    """What the one loop driver can run; everything else is refused by
    what the file says, never by its name."""
    algo = t.get("algorithm")
    if algo in ALGORITHMS_RESERVED:
        raise NotImplementedError(
            f"traffic {t['name']}: algorithm {algo!r} is reserved and not built "
            "yet; see PERF.md section 7, Open questions, table 'cells'")
    if algo not in ALGORITHMS_BUILT:
        raise ValueError(f"traffic {t['name']}: unknown algorithm {algo!r}")
    if t.get("group_mesh") is not None or t["chips_per_group"] != 1:
        raise NotImplementedError(
            f"traffic {t['name']}: a group mesh (HSDP) is reserved and not built "
            "yet; see PERF.md section 7, Open questions, table 'cells'")
    for key in ("groups", "batch_per_group", "seq_len", "warmup_steps", "trace_steps"):
        if not isinstance(t[key], int) or t[key] < 1:
            raise ValueError(f"traffic {t['name']}: {key} must be a positive integer")
    if t["warmup_steps"] < 3:
        raise ValueError("the comparison with the reference reads the first three steps")
    seen = set()
    for kill in t["kills"]:
        g, at = kill["group"], kill["at_measured_step"]
        if not 1 <= g < t["groups"]:
            raise ValueError(
                f"traffic {t['name']}: a kill names group {g}; group 0 keeps the "
                "window's clock and is never killed")
        if g in seen:
            raise NotImplementedError("one kill per group and window, so far")
        if at < 1:
            raise ValueError("a kill lands at the start of measured step 1 or later")
        seen.add(g)
