"""Client layer: the 99th percentile of request latency over the window.  A
layer metric in every cell: in a closed loop at saturation it swings with
the queue, and in the open loop it is set by how many of the server's rare
stalls (40-500 ms) the window caught — spread 109-117 % over 20 s windows and
50 % over 50 s windows on the chip (PR 22): too unsteady to hold a bound."""
import numpy as np


def read(obs):
    if len(obs.latency_ms) < 1000:
        return None
    return float(np.percentile(obs.latency_ms, 99))
