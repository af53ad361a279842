"""The gated short convolution's share of its roofline inside the grad step:
the least time the chip could take for the operations and bytes the operator
needs (the family's ``shortconv_work``: forward and backward of one layer, the
two projections and the taps, ``h`` in, ``y`` out and the weights once, no
intermediate; ``harness/peaks.py``) over the device time under the program's
scope ``shortconv`` (``shortconv_ms``).  The forward counts twice where the
trace shows it recomputed under remat (rows under ``rematted_computation``),
as ``kda_roofline_pct`` counts its.  Plain XLA, no kernel: the share says how
far the two products with the elementwise chain between them are from the
operator done in one pass."""

from benchmarks.harness import peaks


def read(run):
    family = run.get("family")
    if not hasattr(family, "shortconv_work") or not hasattr(family, "scope_rows"):
        return None
    rows = family.scope_rows(run, ("shortconv",))
    if rows is None:
        return None
    spent = sum(op["seconds"] for op in rows)
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    if not runs or not spent:
        return 0.0  # no device ran it (a rehearsal on the CPU)
    sizes = run["sizes"]
    work = family.shortconv_work(sizes, run["traffic"]["batch_per_group"], run["traffic"]["seq_len"])
    layers = sizes["layer_types"][:sizes[family.CUT_KEYS["layers"]]].count("conv")
    forwards = 2 if any("rematted_computation" in op["op_name"] for op in rows) else 1
    least = layers * len(runs) * sum(
        times * peaks.roofline_seconds(run["device_kind"], work[part]["flops"], work[part]["bytes"])
        for part, times in (("forward", forwards), ("backward", 1)))
    print(f"shortconv: {layers} layers, forward x{forwards}; per grad step roofline "
          f"{1e3 * least / len(runs):.3f} ms, device {1e3 * spent / len(runs):.3f} ms", flush=True)
    return 100.0 * least / spent
