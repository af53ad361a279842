"""The census of environment knobs: one list, and one way in for a value.

Every ``TORCHFT_*`` name read through ``utils/env.py`` under ``torchft_tpu/``
is on the list below, has a row in an operator's table, and is not the
second way in for a value that already has an argument.  A name joins the
list when two callers need different values of it (an address, a path, a
deployment's timeout, a name a test or an example sets); with one value in
use it is a constant.
"""

import os
import re

import pytest

from torchft_tpu.analysis import env_hygiene
from torchft_tpu.analysis.core import Project

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KNOBS = (
    "TORCHFT_CONNECT_TIMEOUT_SEC",
    "TORCHFT_EVENTS_FILE",
    "TORCHFT_EVENTS_MAX_BYTES",
    "TORCHFT_EVENTS_RING",
    "TORCHFT_FAULTS",
    "TORCHFT_FAULTS_SEED",
    "TORCHFT_FLIGHT_FILE",
    "TORCHFT_FLIGHT_MAX_BYTES",
    "TORCHFT_FLIGHT_RING",
    "TORCHFT_FRAG_REPORT_S",
    "TORCHFT_FRAG_RING",
    "TORCHFT_FRAG_TOPK",
    "TORCHFT_HEAL_SOURCES",
    "TORCHFT_LIGHTHOUSE",
    "TORCHFT_LINK_REPORT_S",
    "TORCHFT_LINK_TOPK",
    "TORCHFT_LINK_WINDOW",
    "TORCHFT_LOCKCHECK",
    "TORCHFT_LOCKCHECK_HOLD_MS",
    "TORCHFT_MANAGER_PORT",
    "TORCHFT_METRICS_EXPORT_INTERVAL_S",
    "TORCHFT_METRICS_PORT",
    "TORCHFT_NATIVE_LIB",
    "TORCHFT_NO_NATIVE_QUANT",
    "TORCHFT_OTEL_RESOURCE_ATTRIBUTES_JSON",
    "TORCHFT_PLAN_VERIFY",
    "TORCHFT_QUANT_CHUNK_ROWS",
    "TORCHFT_QUANT_WIRE",
    "TORCHFT_QUORUM_RETRIES",
    "TORCHFT_QUORUM_TIMEOUT_SEC",
    "TORCHFT_STORE_DIR",
    "TORCHFT_STORE_SPILL_S",
    "TORCHFT_STORE_VERSIONS",
    "TORCHFT_TIMEOUT_SEC",
    "TORCHFT_TOPOLOGY",
    "TORCHFT_TRACE_FILE",
    "TORCHFT_TRACE_SAMPLE",
    "TORCHFT_USE_OTEL",
    "TORCHFT_WATCHDOG_TIMEOUT_SEC",
    "TORCHFT_WIRE_GBPS",
    "TORCHFT_WIRE_RTT_MS",
)

# where an operator looks a knob up
TABLES = ("docs/observability.md", "docs/robustness.md", "docs/static_analysis.md")


@pytest.fixture(scope="module")
def reads():
    project = Project.from_paths([os.path.join(ROOT, "torchft_tpu")], root=ROOT)
    return env_hygiene.knob_reads(project)


@pytest.fixture(scope="module")
def table_rows():
    rows = set()
    for rel in TABLES:
        with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
            for first_cell in re.findall(r"^\|([^|]*)\|", fh.read(), re.M):
                rows.update(re.findall(r"`(TORCHFT_[A-Z0-9_]+)`", first_cell))
    return rows


def test_the_names_read_are_the_names_listed(reads):
    read = {name for name, _file, _line, _twin in reads}
    assert read == set(KNOBS), (
        f"read and not listed: {sorted(read - set(KNOBS))}; "
        f"listed and not read: {sorted(set(KNOBS) - read)}"
    )
    assert len(KNOBS) == len(set(KNOBS))


def test_no_table_lists_a_name_nothing_reads(table_rows):
    assert table_rows - set(KNOBS) == set()


@pytest.mark.parametrize("name", KNOBS)
def test_knob_has_a_row_and_is_no_argument_twin(name, reads, table_rows):
    assert name in table_rows, f"{name} has no row in {TABLES}"
    twins = [
        f"{file}:{line}" for knob, file, line, twin in reads if knob == name and twin
    ]
    assert not twins, (
        f"{name} is the else arm of an argument at {twins}: make its value "
        f"the argument's default"
    )
