"""Model FLOPs of one group's forward + backward (the family's
``flops_per_step``, remat not counted) over the grad step's device time over
the chip's published bf16 peak (``harness/peaks.py``).  While the grad step
runs, not over the step: the idle share is ``device.busy_s`` /
``device.window_s``."""

from benchmarks.harness import peaks


def read(run):
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    if not runs:
        return None
    return (100.0 * run["flops_per_group_step"] / (sum(runs) / len(runs))
            / peaks.peak_flops(run["device_kind"]))
