"""The healer's ``heal_manifest.wait`` of the healing step: inside the fetch of
the primary's manifest, the long-poll while the source is still encoding and
hashing its state.  The largest over the kills."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["heal_manifest.wait"] for r in stats.healing(run["records"])
            if "heal_manifest.wait" in r["phases"]]
    return max(rows) if rows else None
