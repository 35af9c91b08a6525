"""Wire layer: the reply path's own work beside its waiting — encoding the
frame's replies and ``write`` + ``drain`` (``reply.encode`` + ``reply.write``
spans), per frame, median over the slice's frames."""
from benchmark import spans

PARTS = ("reply.encode", "reply.write")


def read(obs):
    return spans.median_ms(sum(spans.stage_us(f, p) for p in PARTS) for f in obs.frames
                           if any(s["name"] in PARTS for s in f["spans"]))
