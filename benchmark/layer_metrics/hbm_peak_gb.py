"""Peak device memory of the process after the window, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``)."""

LAYER = "device"
UNIT = "GB"
MOVES = "queries_per_s"


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
