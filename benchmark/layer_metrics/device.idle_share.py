"""Device layer: share of the traced slice in which the chip ran nothing,
mean over the chips used, in percent."""


def read(obs):
    d = obs.device
    if not d or not d["window_s"]:
        return None
    return 100.0 * (1.0 - sum(d["busy_s"]) / len(d["busy_s"]) / d["window_s"])
