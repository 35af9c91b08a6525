"""Frame dispatch layer: what a frame spent between ``run_in_executor`` and
its worker's first line (``hop`` spans: the queue for a pool thread plus the
thread switch), summed over the frame's hops, median over the slice's
frames."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "hop")
