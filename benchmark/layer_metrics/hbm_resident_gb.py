"""What the executor keeps resident on the device once the window has
closed: the column planes, zone maps and indexes of every batch it built
for the cell's statements (``hbm_stats()["resident_bytes"]``). Beside
``hbm_peak_gb`` it says how much of the peak is table and how much is a
launch's working space."""

LAYER = "device"
UNIT = "GB"
MOVES = "queries_per_s"


def read(run):
    resident = run["resident_bytes"]
    return resident / 1e9 if resident else None
