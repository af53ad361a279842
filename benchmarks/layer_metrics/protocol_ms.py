"""Per-step delta of ``Manager.phase_times()``: ``quorum_wait`` + ``commit`` +
``host_sync`` (what the caller thread waits for the protocol), median."""

from benchmarks.harness import stats


def read(run):
    rows = [sum(r["phases"].get(k, 0.0) for k in ("quorum_wait", "commit", "host_sync")) for r in stats.steady(run["records"])]
    return 1e3 * stats.median(rows) if rows else None
