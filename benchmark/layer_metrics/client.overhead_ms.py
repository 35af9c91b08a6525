"""Client layer: what a request costs outside the server — blob packing, the
socket both ways, reply decoding, and the wait for the server to read the
socket.  Median client-side latency of the requests of the traced slice
minus the server's share of one request there (median frame total times the
frames one request was parsed as)."""
import numpy as np


def read(obs):
    if not obs.frames or not len(obs.slice_latency_ms) or not obs.slice_requests:
        return None
    per_request = len(obs.frames) / obs.slice_requests
    server_ms = float(np.median([f["total_us"] for f in obs.frames])) / 1e3
    return float(np.median(obs.slice_latency_ms)) - server_ms * per_request
