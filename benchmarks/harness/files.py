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
from typing import Any, Dict, List

from benchmarks.harness import model

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

ALGORITHMS_BUILT = ("ddp",)
ALGORITHMS_RESERVED = ("diloco", "local_sgd")

# a key of this kind is a width in any family, and no width is ever cut; a
# family adds its own under WIDTH_KEYS (experts per token, the router's width)
WIDTH_MARKS = ("hidden", "intermediate", "latent", "state", "proj", "window", "chunk",
               "head_size", "expand", "expansion", "per_tok")
WIDTH_ENDINGS = ("_dim", "_rank", "_width")
# the guide's floors (model-configs section 4): what is left is still the model
MIN_LAYERS_AFTER_DENSE = 4
MIN_EXPERTS_HELD = 8
MIN_VOCAB_SHARE = 8  # at least an eighth of the published rows
# what every configuration file sets itself (harness/model.py reads them)
GENERIC_ASSUMED = ("seq_len", "compute_dtype", "param_dtype", "optimizer") + model.HYPER_KEYS


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


def is_width(key: str, family: Any) -> bool:
    cut = set(family.CUT_KEYS.values())
    return key in family.WIDTH_KEYS or (key not in cut and (
        key.endswith(WIDTH_ENDINGS) or any(mark in key for mark in WIDTH_MARKS)))


def check_config(cfg: Dict[str, Any], reduced: "List[str]", family: Any) -> None:
    """A configuration against the guide's floors.  ``reduced`` is the entry's
    list in ``BENCHMARK.json``.  A key of it is a cut where the family says it
    counts layers, experts held or vocabulary rows (``CUT_KEYS``); any other
    is a numeric departure.  No width is ever in it.  Every cut states its
    published value and the chips that share a layer, and keeps to the
    floors: the leading dense layers, then a whole period and four layers or
    more; eight experts or more; an eighth of the vocabulary or more."""
    name = cfg["name"]
    widths = sorted(k for k in reduced if is_width(k, family))
    if widths:
        raise ValueError(f"config {name}: no width is ever cut, and {widths} are widths")
    named = sorted(d.split(":")[0] for d in cfg["departures"])
    if named != sorted(reduced):
        raise ValueError(f"config {name}: departures name {named}, reduced lists {sorted(reduced)}: "
                         "every key that differs from the source says why")
    cuts = {kind: key for kind, key in family.CUT_KEYS.items() if key in reduced}
    if cuts:
        published = cfg.get("published", {})
        missing = sorted(k for k in cuts.values() if k not in published)
        if missing:
            raise ValueError(f"config {name}: a cut states its published value: "
                             f"`published` lacks {missing}")
        chips = cfg["deployment"].get("chips_sharing_a_layer") if isinstance(
            cfg["deployment"], dict) else None
        if not isinstance(chips, int) or chips < 1:
            raise ValueError(f"config {name}: a cut states over how many chips each layer is "
                             "shared: `deployment` is an object with `chips_sharing_a_layer`")
        for key in cuts.values():
            if not 0 < cfg[key] < published[key]:
                raise ValueError(f"config {name}: {key} {cfg[key]} is no cut of the "
                                 f"published {published[key]}")
    sizes = model.sizes_of(cfg)
    if "layers" in cuts:
        pattern = family.layer_pattern(sizes)
        after = cfg[cuts["layers"]] - pattern["leading_dense"]
        if after < pattern["period"]:
            raise ValueError(f"config {name}: {after} layers after the leading dense ones are "
                             f"less than a whole period of {pattern['period']}")
        if after < MIN_LAYERS_AFTER_DENSE:
            raise ValueError(f"config {name}: {after} layers after the leading dense ones, "
                             f"the floor is {MIN_LAYERS_AFTER_DENSE}")
    if "experts" in cuts and cfg[cuts["experts"]] < MIN_EXPERTS_HELD:
        raise ValueError(f"config {name}: {cfg[cuts['experts']]} experts held, "
                         f"the floor is {MIN_EXPERTS_HELD}")
    if "vocab" in cuts and MIN_VOCAB_SHARE * cfg[cuts["vocab"]] < cfg["published"][cuts["vocab"]]:
        raise ValueError(f"config {name}: {cfg[cuts['vocab']]} rows are less than an eighth "
                         f"of the published vocabulary {cfg['published'][cuts['vocab']]}")
    for key in GENERIC_ASSUMED + tuple(family.ASSUMED_KEYS):
        if key not in cfg["assumed"]:
            raise ValueError(f"config {name}: {key} is set by the file and must be under `assumed`")
    if family.n_params(sizes) != cfg["params"]:
        raise ValueError(f"config {name}: `params` is not what the family counts for these sizes")


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


def load_config_entry(name: str) -> Dict[str, Any]:
    """The configuration's entry in ``BENCHMARK.json``: source, file, reduced, why."""
    found = [c for c in load_benchmark_json()["configs"] if c["name"] == name]
    if len(found) != 1:
        raise FileNotFoundError(f"BENCHMARK.json has no configuration {name!r}")
    return found[0]


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
