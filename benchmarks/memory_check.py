"""On the chip: is ``device.grad_step_peak_bytes`` (the runtime's
``bytes_in_use`` before a grad step + that step's outputs and temporaries from
``memory_analysis()``) what the chip really holds during the step?  The
runtime's counters leave a running program's temporaries out, so this
measures the peak another way: with one group's state of a cell
(params + adamw) on the chip it fills the rest with a filler and finds, by
bisection in blocks of 64 MiB, the largest filler beside which the cell's own
compiled grad step still runs.  ``bytes_limit`` less that filler is the peak.

    python3 benchmarks/memory_check.py --workload ddp1-steady

Prints one JSON object: the counters, the compiled step's sizes, their sum,
and the bracket the filler gives.  PERF.md section 4 holds the reading (the
sum read 8 % above the bracket).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK = 64 * 2**20


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import files, model

    cell = files.load_workload(args.workload)
    config = files.load_config(cell["config"])
    traffic = files.load_traffic(cell["traffic"])
    sizes = model.sizes_of(config)
    family = files.load_family(config["family"])
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: jax sees platform {dev.platform!r}", file=sys.stderr)
        return 1
    on_dev = SingleDeviceSharding(dev)
    batch, seq = traffic["batch_per_group"], traffic["seq_len"]

    # one group's state and programs, built as harness/loop.py builds them
    make = family.make_weights_fn(sizes)
    key = jax.device_put(model.seed_key(args.seed), on_dev)
    params = jax.jit(make, out_shardings=on_dev)(key)
    opt_state = jax.jit(model.optimizer(sizes).init, out_shardings=on_dev)(params)
    grad_step = family.make_grad_step(sizes, seq).lower(
        jax.eval_shape(make, key),
        jax.ShapeDtypeStruct((batch, seq), np.int32, sharding=on_dev)).compile()
    analysis = grad_step.memory_analysis()
    toks = jax.device_put(model.tokens_for(model.vocab_rows(family, sizes), batch, seq, args.seed, 0, 0), on_dev)
    block = jax.jit(lambda: jnp.zeros((BLOCK,), jnp.uint8), out_shardings=on_dev)

    before = dev.memory_stats()
    loss, grads = grad_step(params, toks)
    loss = float(loss)
    counter = dev.memory_stats()
    del grads

    def fits(n_blocks: int) -> bool:
        filler = None
        try:
            filler = [block() for _ in range(n_blocks)]
            jax.block_until_ready(filler)
            _, g = grad_step(params, toks)
            jax.block_until_ready(g)
            return True
        except Exception as e:  # noqa: BLE001 - only an out-of-memory is an answer
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return False
        finally:
            del filler

    lo, hi = 0, int(counter["bytes_limit"]) // BLOCK + 1  # fits(lo), not fits(hi)
    if not fits(lo):
        raise RuntimeError("the grad step does not run with no filler at all")
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    limit = int(counter["bytes_limit"])
    print(json.dumps({
        "workload": args.workload, "device_kind": dev.device_kind, "loss": loss,
        "groups_in_the_cell_on_this_chip": traffic["groups"] if cell["chips"] == 1 else 1,
        "bytes_limit": limit,
        "runtime_peak_bytes_in_use": int(counter["peak_bytes_in_use"]),
        "bytes_in_use_before_the_step": int(before["bytes_in_use"]),
        "grad_step_temp_bytes": int(analysis.temp_size_in_bytes),
        "grad_step_output_bytes": int(analysis.output_size_in_bytes),
        "grad_step_peak_bytes_as_the_harness_reports_it": int(
            before["bytes_in_use"] + analysis.output_size_in_bytes
            + analysis.temp_size_in_bytes),
        "largest_filler_that_fits_bytes": lo * BLOCK,
        "smallest_filler_that_does_not_bytes": hi * BLOCK,
        "peak_by_filler_bytes": [limit - hi * BLOCK, limit - lo * BLOCK],
    }), flush=True)
    del opt_state
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
