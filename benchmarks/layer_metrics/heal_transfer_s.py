"""The healer's ``heal_wire`` + ``heal_decode`` of the healing step.  The largest
over the kills."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"].get("heal_wire", 0.0) + r["phases"].get("heal_decode", 0.0)
            for r in stats.healing(run["records"])]
    return max(rows) if rows else None
