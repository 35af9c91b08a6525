"""Scheduler layer: operations refused by admission during the window
(METRICS ``rtpu_qos_shed_ops``, after minus before).  The benchmark's
traffic sets no tenant budget: anything but 0 is a fault."""


def read(obs):
    a, b = obs.metrics_before.get("rtpu_qos_shed_ops"), obs.metrics_after.get("rtpu_qos_shed_ops")
    if a is None or b is None:
        return None
    return float(b - a)
