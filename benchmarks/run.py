"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (README.md).
Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for: nothing falls back to the CPU.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: "list[str] | None" = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.harness.cell import Refused, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_process_start=_T_START)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    # each number compared beside its limit, as the last lines on standard error
    for name, row in result["compared"].items():
        print(f"compared: {name} = {row['value']} limit {row['limit']} "
              f"{'ok' if row['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - reported, then the process ends
        import traceback

        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # a failed run can leave group threads parked in a collective or on
        # the device: do not wait for them on the way out
        os._exit(1)
    sys.stdout.flush()
    os._exit(code)
