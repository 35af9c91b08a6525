"""Frame dispatch layer: point commands (single-item ``BF.ADD`` /
``BF.EXISTS``) answered a device dispatch issued for them, over the window
(METRICS ``rtpu_point_cmds_total`` over ``rtpu_point_dispatches_total``, after
minus before).  1.0 where every command is dispatched and fetched alone;
what a window of commands formed across connections raises.  None on a
program without the two series, or a window without such a dispatch."""
from benchmark import counters


def read(obs):
    cmds = counters.delta(obs, "rtpu_point_cmds_total")
    dispatches = counters.delta(obs, "rtpu_point_dispatches_total")
    if cmds is None or not dispatches:
        return None
    return cmds / dispatches
