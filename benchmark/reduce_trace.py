"""From a profiler trace (``*.xplane.pb``) to device busy time, per-program
and per-operation device time, and the longest idle gaps.

    python benchmark/reduce_trace.py <trace dir or .xplane.pb> <tpu|cpu> \
        [--mark-wall-ns N --stop-wall-ns N]

prints one JSON object.  It runs as a process of its own after the server
has exited: reading the trace needs ``jax.profiler.ProfileData`` (jax is
imported, no backend is touched), and the benchmark's parent stays off jax.

What counts as the device: on a TPU the planes named ``/device:TPU:<n>`` —
busy is the union of the intervals on the ``XLA Ops`` line (every operation
the chip ran, so a transfer-only or copy program counts too), programs are
the events of the ``XLA Modules`` line.  ``cpu`` exists for the rehearsal of
the script only: the XLA CPU client's threads on ``/host:CPU`` stand in, and
nothing read from them is a device number.

The window: the launcher puts one host annotation (``bench.mark``) into the
trace right after starting it and says when, on the wall clock, it did so
and when it asked the trace to stop; that fixes the trace clock against the
wall clock (for joining gaps with the server's frame spans) and bounds the
window device time is clipped to.  Without a mark the window is first event
to last event.
"""
import glob
import json
import os
import re
import sys

MARK = "bench.mark"
TOP = 10
_HASH = re.compile(r"\(\d+\)$")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def union(intervals):
    """Sorted, merged copy of [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _device_lines(planes, platform: str):
    """{device number: {"ops": [events], "modules": [events]}}; an event is
    (name, start_ns, end_ns)."""
    devices = {}
    for plane in planes:
        if platform == "tpu":
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if not m:
                continue
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind:
                    dev[kind] += _events(line)
        elif plane.name == "/host:CPU":
            dev = devices.setdefault(0, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    dev["ops"] += _events(line)
    for dev in devices.values():
        if not dev["ops"]:  # a trace without the per-operation line
            dev["ops"] = dev["modules"]
    return devices


def _mark(planes):
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == MARK:
                    return int(e.start_ns)
    return None


def _top(events, lo, hi, strip_hash: bool):
    total = {}
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            if strip_hash:
                name = _HASH.sub("", name)
            total[name] = total.get(name, 0.0) + (b - a) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])


def reduce_planes(planes, platform: str, mark_wall_ns=None, stop_wall_ns=None) -> dict:
    devices = _device_lines(planes, platform)
    every = [e for d in devices.values() for e in d["ops"]]
    if not every:
        return {"devices": 0, "busy_s": [], "window_s": 0.0}
    mark = _mark(planes)
    to_wall = None
    if mark is not None and mark_wall_ns is not None and stop_wall_ns is not None:
        to_wall = mark_wall_ns - mark
        lo, hi = mark, stop_wall_ns - to_wall
    else:
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    busy, gaps, ops, modules = [], [], [], []
    for n, dev in sorted(devices.items()):
        merged = union((max(a, lo), min(b, hi)) for _nm, a, b in dev["ops"]
                        if min(b, hi) > max(a, lo))
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append({"device": n, "start_ns": a, "seconds": (b - a) / 1e9,
                             "start_wall_ns": None if to_wall is None else a + to_wall})
        ops += [(nm, a, b) for nm, a, b in dev["ops"]]
        modules += [(nm, a, b) for nm, a, b in dev["modules"]]
    gaps.sort(key=lambda g: -g["seconds"])
    return {
        "devices": len(devices),
        "busy_s": busy,
        "window_s": (hi - lo) / 1e9,
        "clock": "wall" if to_wall is not None else "trace",
        "programs": _top(modules, lo, hi, True)[:TOP],
        "ops": _top(ops, lo, hi, False)[:TOP],
        "program_seconds": sum(s for _n, s in _top(modules, lo, hi, True)),
        "gaps": gaps[:TOP],
    }


def _load(path: str):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(find_xplane(path)).planes)


def reduce_file(path: str, platform: str, mark_wall_ns=None, stop_wall_ns=None) -> dict:
    return reduce_planes(_load(path), platform, mark_wall_ns, stop_wall_ns)


def describe(path: str) -> list:
    """Planes, lines, event counts and a few event names: what to look at by
    hand before trusting a reduction of a new kind of trace."""
    out = []
    for plane in _load(path):
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events),
                          "first": [(e.name, int(e.start_ns), int(e.duration_ns))
                                    for e in events[:4]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("platform", choices=("tpu", "cpu"))
    ap.add_argument("--mark-wall-ns", type=int, default=None)
    ap.add_argument("--stop-wall-ns", type=int, default=None)
    ap.add_argument("--describe", action="store_true",
                    help="print the trace's planes and lines instead")
    args = ap.parse_args(argv)
    if args.describe:
        print(json.dumps(describe(args.path), indent=1))
        return 0
    print(json.dumps(reduce_file(args.path, args.platform, args.mark_wall_ns,
                                 args.stop_wall_ns)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
