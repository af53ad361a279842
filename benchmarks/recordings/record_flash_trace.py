"""Records ``flash_v5e.xplane.pb`` + ``flash_v5e.json``, kept beside this file:
one traced run of a cell through ``run_cell`` on the chip, at sizes
small enough to keep (two layers, two heads of 64, 256 positions) with the
program's flash-attention kernels in the grad step.  The tier-1 tests reduce
the recording as a chip run reduces its own trace, and hold every
``device_trace`` reader to the numbers the chip printed.

    chiprun -- python3 benchmarks/recordings/record_flash_trace.py
    cp chiprun_out/flash_v5e.* benchmarks/recordings/

Not part of a benchmark run."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

PRESET = {
    "config": {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
               "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 64,
               "vocab_size": 512},
    "traffic": {"batch_per_group": 2, "seq_len": 256},
    # at widths this small rounding does not average out: not what is recorded
    "limits": {"loss_gap": 1.0, "grad0_norm_gap": 1.0, "delta_norm_gap": 1.0},
}


def strip(src: str, dst: str, span_prefixes=("bench.", "torchft.")) -> None:
    """Keeps what the reduction reads and no more: of a device plane the ``XLA
    Modules`` and ``XLA Ops`` lines, of a host plane the benchmark's and the
    program's own spans.  A recording of one second is megabytes otherwise."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in list(space.planes):
        if plane.name.startswith("/device:"):
            keep = [line for line in plane.lines if line.name in ("XLA Modules", "XLA Ops")]
        elif plane.name.startswith("/host:"):
            keep = []
            for line in plane.lines:
                events = [e for e in line.events
                          if plane.event_metadata[e.metadata_id].name.startswith(span_prefixes)]
                if events:
                    del line.events[:]
                    line.events.extend(events)
                    keep.append(line)
        else:
            space.planes.remove(plane)
            continue
        kept_lines = [xplane_pb2.XLine.FromString(line.SerializeToString()) for line in keep]
        del plane.lines[:]
        plane.lines.extend(kept_lines)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        for mid in [m for m in plane.event_metadata if m not in used]:
            del plane.event_metadata[mid]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def main(platform: str = "tpu") -> int:
    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.harness import cell, files, trace

    out_dir = os.path.join(files.CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    workload = files.load_benchmark_json()["workloads"][0]["name"]
    kept = {}
    find, reduce = trace.find_xplane, trace.reduce

    def find_and_keep(trace_dir):
        path = find(trace_dir)
        strip(path, os.path.join(out_dir, "flash_v5e.xplane.pb"))
        return path

    def reduce_and_keep(loaded, chips, groups, **kw):
        reduced = reduce(loaded, chips, groups, **kw)
        seen = {op["label"] for op in reduced["ops"]}
        kept["names"] = {module: {label: row for label, row in rows.items() if label in seen}
                         for module, rows in kw["names"].items()}
        kept["trace"] = {k: reduced[k] for k in ("window_s", "busy_s", "module_seconds")}
        return reduced

    trace.find_xplane, trace.reduce = find_and_keep, reduce_and_keep
    result = cell.run_cell(workload, 26, 1.0, True, platform=platform, preset=PRESET)
    entry = files.load_workload(workload)
    config = files.load_config(entry["config"])
    with open(os.path.join(out_dir, "flash_v5e.json"), "w") as f:
        json.dump({
            "recorded_with": "benchmarks/recordings/record_flash_trace.py",
            "workload": workload, "config": config["name"], "sizes_over": PRESET["config"],
            "traffic": dict(files.load_traffic(entry["traffic"]), **PRESET["traffic"]),
            "grad_module": next(iter(kept["names"])),
            "device": result["device"], "names": kept["names"], "trace": kept["trace"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }, f, indent=1)
    print(json.dumps({"metrics": result["metrics"], "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    code = main(*sys.argv[1:2])
    sys.stdout.flush()
    os._exit(code)
