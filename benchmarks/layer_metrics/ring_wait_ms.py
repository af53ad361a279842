"""Per-step delta of ``ring.wire.wait``: in every exchange of the allreduce but
its first, the PG worker's wait for the peer's next message to start (the peer
is in the ring and late with this chunk: lock-step jitter, threads contending
for the host's cores, a peer held by its own device-to-host leg).  Timed inside
the program; median over the steps that report the part."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["ring.wire.wait"] for r in stats.steady(run["records"])
            if "ring.wire.wait" in r["phases"]]
    return 1e3 * stats.median(rows) if rows else None
