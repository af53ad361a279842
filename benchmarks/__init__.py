"""torchft_tpu's chip benchmark: one command, cells found by name (README.md)."""
