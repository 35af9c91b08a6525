"""Frame dispatch layer: what a point command waited between its plan and
its device dispatch being issued (``point.wait`` spans: the queue for a
worker and the record's lock where every command goes alone, the wait for
its window where commands are gathered), median over the slice's spans.
None where no frame of the slice has the span."""
from benchmark import spans


def read(obs):
    return spans.median_ms(s["dur_us"] for f in obs.frames for s in f["spans"]
                           if s["name"] == "point.wait")
