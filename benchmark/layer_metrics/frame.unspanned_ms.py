"""Frame dispatch layer: the part of a frame's total that lies in no span —
``total_us`` minus the union of the frame's spans inside [0, total_us] —
median over the slice's frames.  Spans may overlap (``reply`` and its
children, ``readback`` inside ``reply``): the union counts a microsecond
once.  ``recv`` lies before offset 0 and ``host.*`` annotate a pause, not a
stage: neither covers anything here."""
from benchmark import spans
from benchmark.reduce_trace import union


def unspanned_us(frame: dict) -> int:
    total = frame["total_us"]
    inside = [(max(0, s["off_us"]), min(total, s["off_us"] + s["dur_us"]))
              for s in frame["spans"] if not s["name"].startswith("host.")]
    return total - sum(b - a for a, b in union((a, b) for a, b in inside if b > a))


def read(obs):
    return spans.median_ms(unspanned_us(f) for f in obs.frames if f["spans"])
