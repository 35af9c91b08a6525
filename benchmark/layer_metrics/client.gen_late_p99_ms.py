"""Client layer, validity: how late the open-loop generator itself sent a
request (send time minus the later of its due time and its connection
falling free), 99th percentile over the window.  A starved generator would
otherwise read as a fast server."""
import numpy as np


def read(obs):
    if obs.gen_late_ms is None or not len(obs.gen_late_ms):
        return None
    return float(np.percentile(obs.gen_late_ms, 99))
