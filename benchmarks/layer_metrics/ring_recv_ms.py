"""Per-step delta of ``ring.wire.recv``: the messages' headers and payloads
coming in once their first bytes are here, the socket reads of every exchange
of the allreduce.  Timed inside the program; median over the steps that report
the part."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["ring.wire.recv"] for r in stats.steady(run["records"])
            if "ring.wire.recv" in r["phases"]]
    return 1e3 * stats.median(rows) if rows else None
