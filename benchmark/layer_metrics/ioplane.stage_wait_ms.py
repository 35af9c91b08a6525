"""I/O plane: time a dispatch queued behind its device lane's gate, median
over the frames that went through a lane (device-sharded serving only)."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "stage")
