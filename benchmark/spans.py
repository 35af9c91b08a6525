"""From a ``TRACE GET`` reply to per-stage numbers.

The server's stage spans (``redisson_tpu/observe/trace.py``) come over the
wire as ``[id, unix_ms, total_us, verb, n_cmds, class, tenant, [[name,
off_us, dur_us, [k, v, ...]], ...]]`` a frame.  This file turns the reply
into plain dicts and holds the arithmetic the per-layer readers share: a
stage's time in a frame is the sum of its spans there, a stage's self time
is that minus the part of it its child stages cover, and a stage metric is
the median of that over the frames of the traced slice.
"""
import numpy as np

from benchmark.reduce_trace import union

# frames the benchmark's own control commands make; never traffic
ADMIN_VERBS = frozenset(("TRACE", "CONFIG", "INFO", "CLUSTER", "METRICS", "PING",
                         "CLIENT", "HELLO", "AUTH"))


def _text(v) -> str:
    return v.decode(errors="replace") if isinstance(v, (bytes, bytearray)) else str(v)


def parse_frames(reply) -> list:
    """The traffic's frames of one ``TRACE GET n`` reply."""
    frames = []
    for tid, unix_ms, total_us, verb, n_cmds, cls, tenant, spans in reply:
        verb = _text(verb).upper()
        if verb in ADMIN_VERBS:
            continue
        out = []
        for name, off_us, dur_us, attrs in spans:
            kv = {_text(attrs[i]): attrs[i + 1] for i in range(0, len(attrs) - 1, 2)}
            out.append({"name": _text(name), "off_us": int(off_us),
                        "dur_us": int(dur_us),
                        "attrs": {k: (_text(v) if isinstance(v, (bytes, bytearray)) else v)
                                  for k, v in kv.items()}})
        frames.append({"id": int(tid), "unix_ms": int(unix_ms), "total_us": int(total_us),
                       "verb": verb, "n_cmds": int(n_cmds), "class": _text(cls),
                       "spans": out})
    return frames


def stage_us(frame: dict, stage: str) -> int:
    return sum(s["dur_us"] for s in frame["spans"] if s["name"] == stage)


def _covered_us(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    return sum(b - a for a, b in union(intervals))


def self_us(frame: dict, stage: str, children) -> int:
    """Time of ``stage`` spans not covered by spans of ``children``."""
    mine = [(s["off_us"], s["off_us"] + s["dur_us"]) for s in frame["spans"]
            if s["name"] == stage]
    kids = [(s["off_us"], s["off_us"] + s["dur_us"]) for s in frame["spans"]
            if s["name"] in children]
    inside = []
    for a, b in mine:
        inside += [(max(a, c), min(b, d)) for c, d in kids if min(b, d) > max(a, c)]
    return _covered_us(mine) - _covered_us(inside)


def median_ms(values_us):
    """Median of per-frame microseconds, in ms; None when there is nothing."""
    values_us = list(values_us)
    if not values_us:
        return None
    return float(np.median(np.asarray(values_us, np.float64))) / 1e3


def stage_median_ms(frames, stage: str):
    """Median over the frames that have the stage at all."""
    return median_ms(stage_us(f, stage) for f in frames
                     if any(s["name"] == stage for s in f["spans"]))
