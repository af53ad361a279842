"""Per-step delta of the ``quorum_rpc`` phase (the lighthouse round trip on the
async quorum thread), median."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"].get("quorum_rpc", 0.0) for r in stats.steady(run["records"])]
    return 1e3 * stats.median(rows) if rows else None
