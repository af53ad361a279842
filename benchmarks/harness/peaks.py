"""Published peaks: the yardstick's side of every utilisation number, kept
where a PR that claims a gain cannot move it.  The operation count of a model
is its family's (``benchmarks/families/<family>.py`` ``flops_per_step``).

Copied from ``bench.py`` (``_PEAK_TFLOPS``); the original is listed in PERF.md
for a later PR to delete."""

from __future__ import annotations

# dense bf16 peak of one chip, TFLOP/s, by a substring of ``device_kind``
PEAK_BF16_TFLOPS = (
    ("v5 lite", 197.0, "Google Cloud documentation, 'TPU v5e'"),  # a v5e reports "TPU v5 lite"
    ("v5e", 197.0, "Google Cloud documentation, 'TPU v5e'"),
)


def peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, tflops, _source in PEAK_BF16_TFLOPS:
        if key in kind:
            return tflops * 1e12
    raise ValueError(
        f"no published bf16 peak for device kind {device_kind!r}: add it to "
        "PEAK_BF16_TFLOPS with its source before reporting a utilisation")
