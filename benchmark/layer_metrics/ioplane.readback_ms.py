"""I/O plane: fetching a frame's results from the device, median over the
frames that fetched any."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "readback")
