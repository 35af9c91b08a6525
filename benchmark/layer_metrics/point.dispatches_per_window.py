"""Frame dispatch layer: device dispatches issued a window of point commands
(single-item ``BF.ADD`` / ``BF.EXISTS`` of one record, answered together),
over the window (METRICS ``rtpu_point_dispatches_total`` over
``rtpu_point_windows_total``, after minus before).  1.0 where a window is
one program whatever its mix.  None on a program without the two series, or
a window that served no point command."""
from benchmark import counters


def read(obs):
    dispatches = counters.delta(obs, "rtpu_point_dispatches_total")
    windows = counters.delta(obs, "rtpu_point_windows_total")
    if dispatches is None or not windows:
        return None
    return dispatches / windows
