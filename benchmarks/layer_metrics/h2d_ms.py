"""The harness's clock around ``device_put`` of the averaged gradients up to
``block_until_ready``, median over the measured committed steps."""

from benchmarks.harness import stats


def read(run):
    rows = [r["h2d_s"] for r in stats.steady(run["records"])]
    return 1e3 * stats.median(rows) if rows else None
