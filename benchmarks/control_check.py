"""Reads the two numbers every limit of ``correct`` is set from, on the chip
at the cell's own size (PERF.md section 2 holds the readings):

- ``--program-seeds``: the program's first steps against the reference, one
  short run per seed in this one process (``run_cell`` with a minimal window);
- ``--control-seeds``: the control — the reference put in the program's
  place, computed with the operands of every matrix product rounded to the
  nearest precision below the configuration's bfloat16 (``float8_e4m3fn``) —
  against the float32 reference.  The control must come out as not correct.

    python3 benchmarks/control_check.py --workload ddp1-steady \
        --program-seeds 11,12,13 --control-seeds 21,22,23

The benchmark's own runs never run the control; its small-size twin is
``tests/bench_harness/test_bench_reference.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_numbers(workload: str, seed: int, operand_dtype: str, preset=None) -> dict:
    """Reference vs the same reference at lower operand precision, at the
    cell's sizes, on the cell's rows for that seed."""
    import jax

    from benchmarks.harness import correct, files, model
    from benchmarks.reference import train as reference

    preset = preset or {}
    cell = files.load_workload(workload)
    traffic = dict(files.load_traffic(cell["traffic"]))
    traffic.update(preset.get("traffic", {}))
    config = files.load_config(cell["config"])
    sizes = model.sizes_of(config, preset.get("config"))
    family = files.load_family(config["family"])
    devices = model.reference_devices(jax.devices()[:cell["chips"]], traffic)
    batches = model.setup_batches(model.vocab_rows(family, sizes), traffic, seed)
    make = jax.jit(family.make_weights_fn(sizes))
    sides = {}
    for label, dtype in (("reference", None), ("control", operand_dtype)):
        out = reference.run(family.reference_loss, make(model.seed_key(seed)), batches, sizes,
                            model.hyper(sizes), devices, operand_dtype=dtype,
                            stacked=family.STACKED)
        sides[label] = out
    control = dict(sides["control"])
    control["losses"] = {(s, g): v for s, row in enumerate(control["losses"])
                         for g, v in enumerate(row)}
    return correct.against_reference(control, sides["reference"])


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--program-seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-dtype", default="float8_e4m3fn")
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args()

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.harness import files
    from benchmarks.harness.cell import run_cell

    limits = files.load_limits(args.workload)
    for seed in [int(s) for s in args.program_seeds.split(",") if s]:
        t0 = time.perf_counter()
        result = run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps({"program_seed": seed, "correct": result["correct"],
                          "metrics": result["metrics"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t0 = time.perf_counter()
        numbers = control_numbers(args.workload, seed, args.control_dtype)
        failed = sorted(k for k, v in numbers.items() if not v <= limits[k])
        print(json.dumps({"control_seed": seed, "dtype": args.control_dtype,
                          "numbers": numbers, "fails_limit_of": failed,
                          "control_is_not_correct": bool(failed),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
