"""Kernels layer: the point commands' share of their roofline over the traced
slice — the time the chip's HBM peak allows the bytes the slice's verified
commands ASKED for (``benchmark/roofline_bf.py``: k cells read a probe, k
read and written an add, the item in and a flag out; no padding, no
granule) over the device-busy seconds of the whole slice, EVERY program (what
``kernel.device_ms_per_mop`` divides by) — never one program's name, so it
reads the same work whatever implements it.

Probes against adds: the window's ``rtpu_point_cmds_bf_exists_total`` /
``rtpu_point_cmds_bf_add_total`` (after minus before), applied to the
slice's verified operations.  k, the key space and the prefix: the
traffic's.  None where any of that is absent: a program without the
counters, an untraced run."""
from benchmark import counters, roofline, roofline_bf


def read(obs):
    if not obs.device or not obs.slice_ops:
        return None
    exists = counters.delta(obs, "rtpu_point_cmds_bf_exists_total")
    adds = counters.delta(obs, "rtpu_point_cmds_bf_add_total")
    busy_s = sum(obs.device["busy_s"])
    peaks = roofline.the_peaks()
    if exists is None or adds is None or exists + adds <= 0 or not busy_s or peaks is None:
        return None
    add_share = adds / (exists + adds)
    p = obs.params
    nbytes = roofline_bf.bloom_point_bytes(
        obs.slice_ops * (1.0 - add_share), obs.slice_ops * add_share, p["k"],
        roofline_bf.mean_item_bytes(p["key_prefix"], p["key_max"]))
    return 100.0 * roofline.seconds(0.0, nbytes, peaks) / busy_s
