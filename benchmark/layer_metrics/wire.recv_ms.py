"""Wire layer: the frame's arrival — from the socket read that brought its
first byte to the read that completed it (``recv`` span, before the frame's
t0) — median over the slice's frames.  A 1.2 MB frame crosses ~19 reads of
64 KB; the sender's pace, the event loop's turns between reads and the
fruitless parser feeds (``feed_us``) are all in it."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "recv")
