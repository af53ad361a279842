"""The windowed flash-attention kernels' share of their roofline inside the
grad step: the least time the chip could take for the operations and bytes of
the band (the family's ``flash_attn_work`` under its ``FLASH_WINDOW_KERNELS``:
the live pairs inside the window only, so a skipped tile lifts nothing;
``harness/peaks.py``) times the calls the trace shows, over those kernels' own
device time.  ``flash_attn_roofline_pct`` reads these and the global layers'
three together; this one says what the band's tile walk reaches alone."""

from benchmarks.harness import peaks


def read(run):
    family = run.get("family")
    names = getattr(family, "FLASH_WINDOW_KERNELS", None)
    ops = run.get("trace", {}).get("ops")
    if names is None or ops is None:
        return None
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    rows = [op for op in ops if op["module"] == run["grad_module"] and op["kernel"] in names]
    spent = sum(op["seconds"] for op in rows)
    if not runs or not spent:
        return 0.0  # no device ran them (a rehearsal on the CPU)
    work = family.flash_attn_work(
        run["sizes"], run["traffic"]["batch_per_group"], run["traffic"]["seq_len"])
    least = sum(op["calls"] * peaks.roofline_seconds(
        run["device_kind"], work[op["kernel"]]["flops"], work[op["kernel"]]["bytes"]) for op in rows)
    print(f"flash_window: per grad step roofline {1e3 * least / len(runs):.3f} ms, "
          f"device {1e3 * spent / len(runs):.3f} ms", flush=True)
    return 100.0 * least / spent
