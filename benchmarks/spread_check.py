"""On the chip: runs of one cell, one process each as the driver starts them,
and the spread of every end-to-end metric over them: the distance between the
first and the third quartile (``statistics.quantiles(values, n=4)``) as a share
of the median.  PERF.md section 2's rule sets a bound to five times the widest
such spread over the cells, capped at 0.1 and never under 0.01.

    python3 benchmarks/spread_check.py --workload ddp2-steady --seeds 11,12,13,14,15,16 [--out DIR]

The first run of a call may compile; ``setup_s`` of the first run is printed
apart.  Not part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: "list[float]") -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--out", default=None, help="directory for each run's output")
    args = p.parse_args()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
            capture_output=True, text=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            for ext, text in (("out", proc.stdout), ("err", proc.stderr)):
                with open(os.path.join(args.out, f"{args.workload}-{seed}.{ext}"), "w") as f:
                    f.write(text)
        lines = [line for line in proc.stdout.splitlines() if line.startswith("{")]
        if proc.returncode or not lines:
            print(f"seed {seed}: rc {proc.returncode}\n{proc.stderr[-2000:]}", flush=True)
            return 1
        result = json.loads(lines[-1])
        rows.append(result)
        print(json.dumps({"seed": seed, "correct": result["correct"], "failed": result["failed"],
                          "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                          **{k: v["value"] for k, v in result["metrics"].items()}}), flush=True)
    out = {"workload": args.workload, "runs": len(rows),
           "all_correct": all(r["correct"] for r in rows)}
    for name in rows[0]["metrics"]:
        values = [r["metrics"][name]["value"] for r in rows]
        if name == "setup_s":
            out["first_setup_s"], values = values[0], values[1:]
        if len(values) >= 2:
            out[name] = {"median": statistics.median(values), "spread": spread(values),
                         "min": min(values), "max": max(values)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
