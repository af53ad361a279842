"""The healer's ``heal_diff.snapshot`` + ``.encode`` + ``.hash`` of the healing
step: bringing its own state to the host, serialising and hashing it into
the source's fragment layout.  The largest over the kills."""

from benchmarks.harness import stats

PARTS = ("heal_diff.snapshot", "heal_diff.encode", "heal_diff.hash")


def read(run):
    rows = [sum(r["phases"].get(k, 0.0) for k in PARTS) for r in stats.healing(run["records"])
            if "heal_diff.hash" in r["phases"]]
    return max(rows) if rows else None
