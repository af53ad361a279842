"""``ring.wire.arrive``: in the first exchange of a step's allreduce, the PG
worker's wait for the previous rank's first byte, i.e. how much later than
this group that one reached the ring (its grad step on a shared chip, its
device-to-host leg).  The group that comes last reads about 0, so the number
of a step is the largest among its groups; median over the measured committed
steps.  Timed inside the program; nothing at world size 1, and nothing from a
program without the part."""

from benchmarks.harness import stats


def read(run):
    by_step = {}
    for r in stats.steady(run["records"]):
        if "ring.wire.arrive" in r["phases"]:
            by_step.setdefault(r["step"], []).append(r["phases"]["ring.wire.arrive"])
    return 1e3 * stats.median([max(v) for v in by_step.values()]) if by_step else None
