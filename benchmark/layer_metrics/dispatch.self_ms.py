"""Frame dispatch layer: the handler window of a frame minus what it spent
behind a lane gate, in a fused kernel call or fetching results — the
server's own Python per frame.  Median over the slice's frames."""
from benchmark import spans

CHILDREN = ("stage", "kernel", "readback")


def read(obs):
    return spans.median_ms(spans.self_us(f, "dispatch", CHILDREN) for f in obs.frames
                           if spans.stage_us(f, "dispatch"))
