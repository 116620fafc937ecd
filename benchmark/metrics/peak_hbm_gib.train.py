"""Device, runtime counters: what the fullest device held at its peak, in
GiB, read after the window and before the reference runs: live arrays at
their peak (``peak_bytes_in_use``) plus the runtime's reservation for the
largest program's temporaries (``peak_bytes_reserved``); see
``peaks.occupied_bytes``."""

from peaks import occupied_bytes


def read(run: dict):
    peaks = [occupied_bytes(s) for s in run["memory_stats"] if "peak_bytes_in_use" in s]
    return max(peaks) / 2**30 if peaks else None
