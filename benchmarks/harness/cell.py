"""One run of one cell: set-up, the measured window, the comparison with
the reference, the result object.  ``run.py`` is the command line around
``run_cell``; the rehearsal tests call it with the platform they have and a
tiny preset."""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from benchmarks.harness import correct, files, loop, model, stats, trace
from benchmarks.reference import train as reference

# the in-process lighthouse of every cell: a clean shutdown tells it of a
# death at once, so the heartbeat only bounds a hang
JOIN_TIMEOUT_MS = 60_000
HEARTBEAT_TIMEOUT_MS = 2_000


class Refused(Exception):
    """The machine cannot run this cell: no result is printed."""


def device_info() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(devices: "List[Any]", records: "List[Dict[str, Any]]",
                grad_step_bytes: "Dict[int, int]", group_device: "List[Any]") -> Dict[str, int]:
    """The fullest chip.  ``runtime_peak_bytes_in_use`` is the runtime's counter
    unchanged; on the v5e it counts live buffers only and leaves a running
    program's temporaries out.  ``grad_step_peak_bytes`` is, at its largest
    over the steps, the runtime's ``bytes_in_use`` read just before a grad
    step plus that compiled step's outputs and temporaries
    (``memory_analysis()``): an estimate that read 8 % above what
    ``benchmarks/memory_check.py`` measured on the chip by filling the memory
    until the step no longer fits (PERF.md section 4), where the counter alone
    reads 52-83 % below.  ``memory_peak_bytes`` is the larger of the two: the
    buffers' peak may fall outside the grad step (a heal holds two copies of
    the state)."""
    out = {"memory_peak_bytes": 0}
    for d in devices:
        live = int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        step = max(r["live_bytes"] for r in records if group_device[r["group"]].id == d.id)
        step += grad_step_bytes[d.id]
        if max(live, step) >= out["memory_peak_bytes"]:
            out = {"memory_peak_bytes": max(live, step), "runtime_peak_bytes_in_use": live,
                   "grad_step_peak_bytes": step}
    return out


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    platform: str = "tpu",
    preset: "Optional[Dict[str, Dict[str, Any]]]" = None,
    t_process_start: Optional[float] = None,
) -> Dict[str, Any]:
    """``preset`` = {"config": {...}, "traffic": {...}, "limits": {...}} lays a
    test's tiny sizes, and the limits that fit leaves that small, over the
    files'; a chip run passes none."""
    import jax

    import torchft_tpu  # noqa: F401 - builds the native library on first use
    from torchft_tpu.coordination import LighthouseServer

    t_begin = time.perf_counter() if t_process_start is None else t_process_start
    preset = preset or {}
    cell = files.load_workload(workload)
    config = files.load_config(cell["config"])
    traffic = dict(files.load_traffic(cell["traffic"]))
    traffic.update(preset.get("traffic", {}))
    files.check_traffic(traffic)
    sizes = model.sizes_of(config, preset.get("config"))
    family = files.load_family(config["family"])
    files.check_config(config, files.load_config_entry(cell["config"])["reduced"], family)
    family.check(sizes)

    device = device_info()
    if device["platform"] != platform:
        raise Refused(f"jax sees platform {device['platform']!r}, the cell needs {platform!r}")
    if device["count"] < cell["chips"]:
        raise Refused(f"the cell needs {cell['chips']} chip(s), jax sees {device['count']}")
    devices = jax.devices()[:cell["chips"]]
    n_groups = traffic["groups"]
    group_device = [devices[i % len(devices)] for i in range(n_groups)]
    groups_on_chip: "Dict[int, List[int]]" = {}
    for i, d in enumerate(group_device):
        groups_on_chip.setdefault(d.id, []).append(i)

    grad_step = family.make_grad_step(sizes, traffic["seq_len"])
    print(f"setup: {workload} seed {seed} on {device}; {family.n_params(sizes)} params by the "
          f"configuration's shapes; groups {n_groups} x batch {traffic['batch_per_group']} x "
          f"seq {traffic['seq_len']}", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    tracer = trace.Tracer(trace_dir) if traced else None
    shared = loop.Shared(n_groups, seconds, traffic["trace_steps"], tracer)
    fingerprint = loop.make_fingerprint()
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=JOIN_TIMEOUT_MS,
        heartbeat_timeout_ms=HEARTBEAT_TIMEOUT_MS)
    try:
        def group(i: int) -> None:
            loop.group_loop(
                i, shared, name=f"{workload}_{i}", lighthouse_addr=lighthouse.address(),
                family=family, sizes=sizes, traffic=traffic, device=group_device[i],
                grad_step=grad_step, fingerprint=fingerprint, seed=seed)

        loop.run_threads([lambda i=i: group(i) for i in range(n_groups)])
    finally:
        if tracer is not None:
            tracer.stop()
        lighthouse.shutdown()
    gc.collect()
    device.update(memory_peak(devices, shared.records, shared.grad_step_bytes, group_device))

    records, kills = shared.records, shared.kills
    t0, _ = stats.interval(records)
    end_to_end, counts = stats.end_to_end(
        records, kills, traffic["batch_per_group"] * traffic["seq_len"], t0 - t_begin, seconds)
    print(f"window: {counts}", flush=True)
    print(f"end_to_end: {end_to_end}", flush=True)
    lead = [r for r in stats.measured(records) if r["group"] == 0]
    print("steps of group 0, ms: "
          + " ".join(f"{r['step']}:{1e3 * (r['t_end'] - r['t_start']):.0f}" for r in lead), flush=True)
    slow = max(lead, key=lambda r: r["t_end"] - r["t_start"])
    print("slowest: " + str({k: (round(v, 3) if isinstance(v, float) else v)
                             for k, v in slow.items()
                             if k in ("step", "fwdbwd_s", "ring_s", "h2d_s", "update_s", "check_s",
                                      "participants")})
          + " phases " + str({k: round(v, 3) for k, v in slow["phases"].items()}), flush=True)
    for k in kills:
        print(f"kill: group {k['group']} at step {k['step']}, recovered after "
              f"{k['t_recovered'] - k['t_kill']:.3f} s", flush=True)

    # the reference runs with the program's state freed, outside set-up and window
    t_ref = time.perf_counter()
    batches = model.setup_batches(model.vocab_rows(family, sizes), traffic, seed)
    ref_devices = model.reference_devices(devices, traffic)
    weights = jax.jit(family.make_weights_fn(sizes))(model.seed_key(seed))
    ref = reference.run(family.reference_loss, weights, batches, sizes, model.hyper(sizes), ref_devices,
                        stacked=family.STACKED)
    del weights
    print(f"reference: {time.perf_counter() - t_ref:.1f} s on {len(ref_devices)} chip(s)", flush=True)
    numbers = correct.against_reference(shared.first, ref)
    numbers.update(correct.trajectory(records, kills))
    numbers.update(shared.ring_check)
    is_correct, compared = correct.judge(
        numbers, {**files.load_limits(workload), **preset.get("limits", {})})

    result: Dict[str, Any] = {
        "correct": is_correct, "attempted": counts["attempted"], "failed": counts["failed"],
    }
    if traced:
        grad_module = "jit_" + grad_step.__name__
        # what the program calls the grad step's operations, from the
        # executable the window ran: read here, outside set-up and window
        names = {grad_module: trace.hlo_names(
            shared.grad_step_compiled[devices[0].id].as_text())}
        try:
            reduced = trace.reduce(
                trace.load(trace.find_xplane(trace_dir), loop.SPAN_PREFIX),
                [d.id for d in devices], groups_on_chip, names=names)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {len(reduced['ops'])} device operations, their own seconds "
              f"{sum(op['seconds'] for op in reduced['ops']) / len(devices)!r} a chip against busy_s "
              f"{reduced['busy_s']!r}; {sum(1 for op in reduced['ops'] if op['op_name'])} carry the "
              "program's name", flush=True)
        run = {
            "records": records, "kills": kills, "trace": reduced, "sizes": sizes,
            "traffic": traffic, "device_kind": device["kind"], "family": family,
            "grad_module": grad_module,
            "flops_per_group_step": family.flops_per_step(
                sizes, traffic["batch_per_group"], traffic["seq_len"]),
        }
        metrics = {}
        for name, unit in files.reported("per_layer", workload).items():
            value = files.load_layer_metric(name).read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        result["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = {
            name: {"value": end_to_end[name], "unit": unit}
            for name, unit in files.reported("end_to_end", workload).items()}
    result["device"] = device
    result["compared"] = compared
    return result
