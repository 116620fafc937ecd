"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect per chip). A device that is not in the table is an error, not
a default: a share of the wrong peak is worse than none.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30, "ici_bits_per_s": 1600e9},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            "benchmark/peaks.py with its source"
        ) from None


def occupied_bytes(stats: dict) -> int:
    """A device's peak from its ``memory_stats()``. On this runtime
    ``peak_bytes_in_use`` counts live arrays only; a program's temporaries
    are a reservation the runtime keeps (``peak_bytes_reserved`` is the
    largest program's, to the byte: ``tools/memstat_probe.py``), so what the
    device held is the sum (PERF.md section 3, device)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
