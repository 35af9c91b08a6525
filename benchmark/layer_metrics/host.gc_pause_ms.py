"""Host: milliseconds the server process spent in garbage collections during
the window (METRICS ``rtpu_host_gc_pause_seconds_total``, after minus
before; counted while tracing is armed, every generation)."""
from benchmark import counters


def read(obs):
    return counters.delta(obs, "rtpu_host_gc_pause_seconds_total", 1e3)
