"""Device layer: idle share of the busiest chip, in percent (several chips)."""


def read(obs):
    d = obs.device
    if not d or not d["window_s"] or len(d["busy_s"]) < 2:
        return None
    return 100.0 * (1.0 - max(d["busy_s"]) / d["window_s"])
