"""Published peaks: the yardstick's side of every utilisation number, kept
where a PR that claims a gain cannot move it.  The operation count of a model
is its family's (``benchmarks/families/<family>.py`` ``flops_per_step``), and
so are the operations and bytes of its kernels.

Copied from ``bench.py`` (``_PEAK_TFLOPS``); the original is listed in PERF.md
for a later PR to delete."""

from __future__ import annotations

from typing import Tuple

# one chip, by a substring of ``device_kind``: dense bf16 TFLOP/s, HBM GB/s
PEAKS = (
    ("v5 lite", 197.0, 819.0, "Google Cloud documentation, 'TPU v5e'"),  # a v5e reports "TPU v5 lite"
    ("v5e", 197.0, 819.0, "Google Cloud documentation, 'TPU v5e'"),
)


def _row(device_kind: str) -> Tuple[float, float]:
    kind = device_kind.lower()
    for key, tflops, gbps, _source in PEAKS:
        if key in kind:
            return tflops * 1e12, gbps * 1e9
    raise ValueError(
        f"no published bf16 peak for device kind {device_kind!r}: add it to "
        "PEAKS with its source before reporting a utilisation")


def peak_flops(device_kind: str) -> float:
    return _row(device_kind)[0]


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    return _row(device_kind)[1]


def roofline_seconds(device_kind: str, flops: float, nbytes: float) -> float:
    """The least time the chip could take for this many operations and bytes
    to and from its memory: the larger of the two bounds."""
    peak, bandwidth = _row(device_kind)
    return max(flops / peak, nbytes / bandwidth)
