"""Per-step delta of ``ring.wire``: the 2(w-1) exchanges of every bucket, send +
receive + waiting for the peer.  Timed inside the program; median over the
steps that crossed a wire (none at world size 1)."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["ring.wire"] for r in stats.steady(run["records"])
            if "ring.wire" in r["phases"]]
    return 1e3 * stats.median(rows) if rows else None
