"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from ``benchmarks/bench_suite.py`` ``PEAKS`` (the yardstick lives
with the benchmark, where a later PR cannot move it).  A device that is not
in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16 * 10 ** 9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(have {sorted(PEAKS)}); add the chip with its source")
    return PEAKS[device_kind]
