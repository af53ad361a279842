"""Per-step delta of ``ring.d2h``: on the PG worker thread, the wait for the
device and the copy of the gradient leaves to host numpy.  Timed inside the
program; median over the steps that report it (a program without the part
reports nothing)."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["ring.d2h"] for r in stats.steady(run["records"])
            if "ring.d2h" in r["phases"]]
    return 1e3 * stats.median(rows) if rows else None
