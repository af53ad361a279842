"""Median wall time of the steps ``healthy_step_p90_ms`` takes its tail from (committed,
the slowest group's, a recovery's left out by number): the steady statistic
beside the tail."""

from benchmarks.harness import stats


def read(run):
    times = stats.step_times(run["records"], run["kills"])
    return 1e3 * stats.median(times) if times else None
