"""The flash kernels' share of their roofline under the block masks inside
the grad step: the least time the chip could take for the operations and
bytes of the masks' **live** pairs (the family's ``flash_block_work`` under
its ``FLASH_BLOCK_KERNELS``, the clean copy's block-causal call, and
``FLASH_STRICT_KERNELS``, the noised copy's strictly block-causal call on the
clean keys: a skipped tile is neither work done nor work counted, so skipping
lifts nothing; ``harness/peaks.py``) times the calls the trace shows, over
those kernels' own device time.  The noised copy's own block (a thousandth of
the pairs) runs as fusions, not in these kernels: ``attn_diffusion_ms`` holds
it.  A share over 100 % is a wrong count, not a fast kernel."""

from benchmarks.harness import peaks


def read(run):
    family = run.get("family")
    work_of = getattr(family, "flash_block_work", None)
    ops = run.get("trace", {}).get("ops")
    if work_of is None or ops is None:
        return None
    names = family.FLASH_BLOCK_KERNELS + family.FLASH_STRICT_KERNELS
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    rows = [op for op in ops if op["module"] == run["grad_module"] and op["kernel"] in names]
    spent = sum(op["seconds"] for op in rows)
    if not runs or not spent:
        return 0.0  # no device ran them (a rehearsal on the CPU)
    work = work_of(run["sizes"], run["traffic"]["batch_per_group"], run["traffic"]["seq_len"])
    least = sum(op["calls"] * peaks.roofline_seconds(
        run["device_kind"], work[op["kernel"]]["flops"], work[op["kernel"]]["bytes"]) for op in rows)
    print(f"flash_block: per grad step roofline {1e3 * least / len(runs):.3f} ms, "
          f"device {1e3 * spent / len(runs):.3f} ms", flush=True)
    return 100.0 * least / spent
