"""I/O plane: the share of the bytes the grouped result fetch brought to the
host during the window that no reply owed (METRICS
``rtpu_gather_bytes_fetched_total`` and ``rtpu_gather_bytes_owed_total``,
after minus before): 100 x (fetched - owed) / fetched.  The fetch moves whole
device values; a reply may own only a slice of one (a command's rows of a
fused run's bucket).  None on a program without the two series, or a window
that fetched nothing."""
from benchmark import counters


def read(obs):
    fetched = counters.delta(obs, "rtpu_gather_bytes_fetched_total")
    owed = counters.delta(obs, "rtpu_gather_bytes_owed_total")
    if fetched is None or owed is None or fetched <= 0:
        return None
    return 100.0 * (fetched - owed) / fetched
