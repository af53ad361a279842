"""Per-step delta of the ``ring`` phase: collective submit to completion.  Today
it holds the device-to-host copy and the host passes too; median."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"].get("ring", 0.0) for r in stats.steady(run["records"])]
    return 1e3 * stats.median(rows) if rows else None
