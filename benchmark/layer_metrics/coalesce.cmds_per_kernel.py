"""Coalescer layer: commands fused into one kernel call, over the slice
(``members`` of every ``kernel`` span).  Frames of one command never reach
the coalescer: no ``kernel`` span, no number."""


def read(obs):
    kernels = [s for f in obs.frames for s in f["spans"] if s["name"] == "kernel"]
    if not kernels:
        return None
    return sum(int(s["attrs"].get("members", 1)) for s in kernels) / len(kernels)
