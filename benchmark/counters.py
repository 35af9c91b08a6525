"""From two METRICS scrapes to what a window added.

The server's ``*_total`` series only grow, so a window's share of one is the
scrape after it minus the scrape before it.  A server that has no such
series (an older program) gives no value, not zero."""


def delta(obs, series: str, scale: float = 1.0):
    """``series`` after the window minus before it, times ``scale``; None
    where either scrape lacks it."""
    a, b = obs.metrics_before.get(series), obs.metrics_after.get(series)
    if a is None or b is None:
        return None
    return float(b - a) * scale
