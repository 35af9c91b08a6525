"""Coalescer layer: the share of the filter planes the fused runs stacked on
the device during the window that no command asked for (METRICS
``rtpu_coalesce_planes_stacked_total`` and ``rtpu_coalesce_planes_asked_total``,
after minus before): 100 x (stacked - asked) / stacked.  A stacked run pads
its planes to a static count so that one program serves every composition of
a frame; a padding plane is copied and never probed.  None on a program
without the two series, or a window that stacked no plane."""
from benchmark import counters


def read(obs):
    stacked = counters.delta(obs, "rtpu_coalesce_planes_stacked_total")
    asked = counters.delta(obs, "rtpu_coalesce_planes_asked_total")
    if stacked is None or asked is None or stacked <= 0:
        return None
    return 100.0 * (stacked - asked) / stacked
