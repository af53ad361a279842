"""What ``step_p90_ms`` is, for the cells whose window holds too few steps to
bound it: nearest-rank p90 of the wall time of committed steps, a step's time
the slowest group's, the steps of a recovery left out by number.  Of seven to
ten samples it is the largest or the second largest."""

from benchmarks.harness import stats


def read(run):
    times = stats.step_times(run["records"], run["kills"])
    return 1e3 * stats.nearest_rank(times, 0.9) if times else None
