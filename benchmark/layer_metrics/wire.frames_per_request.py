"""Wire layer: server frames one client request was parsed as (a pipelined
frame larger than one socket read arrives in pieces, and the coalescer fuses
only within a piece)."""


def read(obs):
    if not obs.frames or not obs.slice_requests:
        return None
    return len(obs.frames) / obs.slice_requests
