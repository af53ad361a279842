"""Per-step delta of ``ring.pack`` + ``ring.reduce`` + ``ring.unpack``: the host
passes over the gradient inside the collective (bucket concat and pad-in
copy, the in-place reduce, cast back, split, the AVG division).  Timed
inside the program; median over the steps that report the parts."""

from benchmarks.harness import stats

PARTS = ("ring.pack", "ring.reduce", "ring.unpack")


def read(run):
    rows = [sum(r["phases"].get(k, 0.0) for k in PARTS) for r in stats.steady(run["records"])
            if "ring.pack" in r["phases"]]
    return 1e3 * stats.median(rows) if rows else None
