"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest chip
(GiB)."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
