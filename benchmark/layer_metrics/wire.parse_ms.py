"""Wire layer: RESP bytes to command list, median over the slice's frames."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "parse")
