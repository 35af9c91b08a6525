"""Wire layer: dispatch done to reply bytes written (encoding, and the wait
for results that ride the writer's queue), median over the slice's frames."""
from benchmark import spans


def read(obs):
    return spans.stage_median_ms(obs.frames, "reply")
