"""The healer's ``heal_apply`` of the healing step: the user's
``load_state_dict`` of the healed state, host arrays back onto the device.
The largest over the kills."""

from benchmarks.harness import stats


def read(run):
    rows = [r["phases"]["heal_apply"] for r in stats.healing(run["records"])
            if "heal_apply" in r["phases"]]
    return max(rows) if rows else None
