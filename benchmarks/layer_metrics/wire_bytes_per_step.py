"""``Work.wire_bytes`` of the step's gradient allreduce as group 0 counts it,
median over the measured committed steps (0 at world size 1)."""

from benchmarks.harness import stats


def read(run):
    rows = [float(r["wire_bytes"]) for r in stats.steady(run["records"])
            if r["group"] == 0 and r["wire_bytes"] is not None]
    return stats.median(rows) if rows else None
