"""Vote plus ``Optimizer.update`` (one donated jit) to ``block_until_ready``: the
harness's clock around both, median over the measured committed steps."""

from benchmarks.harness import stats


def read(run):
    rows = [r["update_s"] for r in stats.steady(run["records"])]
    return 1e3 * stats.median(rows) if rows else None
