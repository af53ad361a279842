"""The tiny preset the rehearsals hand to ``run_cell`` (as an argument, never
a CLI flag or an environment variable), and one cached run per cell."""

import functools

TINY = {
    "config": {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 32,
        "vocab_size": 256, "attn_impl": "dense",
    },
    "traffic": {"batch_per_group": 2, "seq_len": 128},
    # leaves of 64 x 64 do not average rounding noise out as a layer of the
    # published widths does: the three precision limits are those of this
    # size (bfloat16 on this CPU reads 0.017 / 0.006 / 1.5e-4 at most); the
    # exact limits stay the cell's
    "limits": {"loss_gap": 1e-3, "grad0_norm_gap": 0.06, "delta_norm_gap": 0.02},
}

# wide enough that rounding noise averages out inside a leaf, as it does at
# the published widths, so that the lower-precision control separates
WIDE = {
    "config": {
        "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "vocab_size": 512, "attn_impl": "dense",
    },
    "traffic": {"batch_per_group": 4, "seq_len": 256},
}

WINDOW_S = 1.0


@functools.lru_cache(maxsize=None)
def rehearsal(cell: str, traced: bool):
    """One CPU run of a cell file through the harness's run function."""
    from benchmarks.harness.cell import run_cell

    return run_cell(cell, 2**31 + 77, WINDOW_S, traced, platform="cpu", preset=TINY)
