"""Coalescer layer: the share of the commands the server offered to its
coalescer during the window that rode a stacked dispatch (METRICS
``rtpu_coalesce_cmds_fused_total`` over ``rtpu_coalesce_cmds_offered_total``,
after minus before): 100 x fused / offered.  Offered are the commands of
device buckets and of the sequential path's runs; a command that is not
fused takes the per-record path, a dispatch and up to three programs of its
own.  None on a program without the two series, or a window that offered no
command."""
from benchmark import counters


def read(obs):
    fused = counters.delta(obs, "rtpu_coalesce_cmds_fused_total")
    offered = counters.delta(obs, "rtpu_coalesce_cmds_offered_total")
    if fused is None or offered is None or offered <= 0:
        return None
    return 100.0 * fused / offered
