"""Scheduler layer: classification, tenant charge and the wait behind the
bulk gate, median over the slice's frames."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "qos")
