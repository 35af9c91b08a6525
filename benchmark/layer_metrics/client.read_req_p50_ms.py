"""Client layer: the median latency of the read frames alone — the requests
whose operation count is the mix's ``pairs_per_read`` (one pipelined merge +
estimate over that many counter pairs).  It is the frame a dashboard waits
for: adds are acknowledged at enqueue, a read waits for every add program
queued before it.  In a mix with no such parameter there is nothing to read."""
import numpy as np


def read(obs):
    per_read = obs.params.get("pairs_per_read")
    if per_read is None:
        return None
    reads = obs.latency_ms[obs.request_ops == per_read]
    if not len(reads):
        return None
    return float(np.median(reads))
