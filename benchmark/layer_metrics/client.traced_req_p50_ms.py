"""Client layer: ``req_p50_ms`` of this traced run — the median over its whole
window of the same samples (``benchmark/latency.py``: requests, or cycles
where the mix says so), client clock.  Against ``req_p50_ms`` of the untraced
runs it is the cost of tracing."""
import numpy as np


def read(obs):
    if not len(obs.judged_ms):
        return None
    return float(np.median(obs.judged_ms))
