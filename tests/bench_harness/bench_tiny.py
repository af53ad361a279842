"""The presets the rehearsals hand to ``run_cell`` (as an argument, never a CLI
flag or an environment variable), and one cached run per cell.  A preset is
its family's: ``tiny/<family>.json`` holds the tiny and the wide sizes in that
family's own keys, the limits that fit leaves that small, the float32 parity
tolerances and a worked operation count; this file picks by the
configuration's family and knows none."""

import functools
import json
import os

from benchmarks.harness import files

TINY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
WINDOW_S = 1.0


@functools.lru_cache(maxsize=None)
def of_family(family):
    with open(os.path.join(TINY_DIR, family + ".json")) as f:
        data = json.load(f)
    assert data["family"] == family
    return data


def of_config(config_name):
    return of_family(files.load_config(config_name)["family"])


def preset(cell, which="tiny"):
    """``{"config", "traffic", "limits"}`` of the cell's configuration's family."""
    data = of_config(files.load_workload(cell)["config"])[which]
    return {k: data[k] for k in ("config", "traffic", "limits") if k in data}


@functools.lru_cache(maxsize=None)
def rehearsal(cell, traced):
    """One CPU run of a cell file through the harness's run function."""
    from benchmarks.harness.cell import run_cell

    return run_cell(cell, 2**31 + 77, WINDOW_S, traced, platform="cpu", preset=preset(cell))
