"""The healthy step's tail: nearest-rank p90 of the wall time of committed
steps, a step's time the slowest group's, the steps of a recovery left out by
number (``stats.end_to_end`` computes the same as ``step_p90_ms``).  Per layer
in every cell, bound in none: of seven to ten samples it is the largest or the
second largest, of the 26 beside a recovery the third largest (runs spread by
up to 8 % of it), and where no group is killed its runs spread by 0.4 % on one
machine and 2.6 % on another, so no bound is neither too tight nor too loose
(PERF.md section 2)."""

from benchmarks.harness import stats


def read(run):
    times = stats.step_times(run["records"], run["kills"])
    return 1e3 * stats.nearest_rank(times, 0.9) if times else None
