"""Frame dispatch layer: what a frame waited for the event loop to come back
to it after a worker had finished its part — the ``wake`` spans (a worker
job's last line -> the first line of the frame's coroutine after its
``await``) and ``reply.wake`` (the overlapped force job's last line -> the
writer task has its result), summed a frame, median over the slice's frames
that have one.  The mirror image of ``executor.hop_ms``: a hop says how long
a worker was waited for, a wake how long the loop was.  None on a program
that records no such span."""
from benchmark import spans

WAKES = ("wake", "reply.wake")


def read(obs):
    return spans.median_ms(
        sum(s["dur_us"] for s in f["spans"] if s["name"] in WAKES)
        for f in obs.frames if any(s["name"] in WAKES for s in f["spans"]))
