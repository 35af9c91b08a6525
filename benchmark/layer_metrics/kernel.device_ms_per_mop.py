"""Kernel layer: device time per million verified operations — the seconds
the chips ran anything during the traced slice (summed over chips) over the
operations whose requests completed in it."""


def read(obs):
    if not obs.device or not obs.slice_ops:
        return None
    return sum(obs.device["busy_s"]) * 1e3 / (obs.slice_ops / 1e6)
