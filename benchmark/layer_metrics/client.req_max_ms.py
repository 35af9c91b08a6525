"""Client layer: the slowest request of the window — the size of the worst
stall a user met."""


def read(obs):
    return float(obs.latency_ms.max()) if len(obs.latency_ms) else None
