"""Client layer: the median request latency of this traced run over its whole
window, client clock.  Against ``req_p50_ms`` of the untraced runs it is the
cost of tracing."""
import numpy as np


def read(obs):
    if not len(obs.latency_ms):
        return None
    return float(np.median(obs.latency_ms))
