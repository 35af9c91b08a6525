"""Wire layer: the waiting inside ``reply`` — for the overlapped readback
future, or in the writer task's queue (``reply.wait`` span) — median over
the slice's frames."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "reply.wait")
