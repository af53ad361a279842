"""The flash-attention kernels' share of their roofline inside the grad step:
the least time the chip could take for the operations and bytes each call
needs (the family's ``flash_attn_work``: the causal half, forward and the two
backward kernels; ``harness/peaks.py``: 197 TFLOP/s, 819 GB/s) times the calls
the trace shows (remat runs the forward twice), over the kernels' own device
time.  A kernel is found by the name the program gives it: the Mosaic module
of its ``pallas_call`` (``run["trace"]["ops"][i]["kernel"]``)."""

from benchmarks.harness import peaks


def read(run):
    work_of = getattr(run.get("family"), "flash_attn_work", None)
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    if work_of is None or not runs:
        return None
    work = work_of(run["sizes"], run["traffic"]["batch_per_group"], run["traffic"]["seq_len"])
    least = spent = flops = nbytes = 0.0
    for op in run["trace"]["ops"]:
        if op["module"] == run["grad_module"] and op["kernel"] in work:
            need = work[op["kernel"]]
            least += op["calls"] * peaks.roofline_seconds(
                run["device_kind"], need["flops"], need["bytes"])
            flops += op["calls"] * need["flops"]
            nbytes += op["calls"] * need["bytes"]
            spent += op["seconds"]
    if not spent:
        return None
    print(f"flash_attn: per grad step {flops / len(runs):.6g} operations, {nbytes / len(runs):.6g} "
          f"bytes, roofline {1e3 * least / len(runs):.3f} ms, device {1e3 * spent / len(runs):.3f} ms",
          flush=True)
    return 100.0 * least / spent
