"""The healer's ``heal_manifest`` + ``heal_diff`` of the healing step: waiting for
the source's manifest and hashing its own state.  The largest over the kills."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"].get("heal_manifest", 0.0) + r["phases"].get("heal_diff", 0.0)
            for r in stats.healing(run["records"])]
    return max(rows) if rows else None
