"""Host: times the server's event loop woke 40 ms or more late during the
window — a collection, a worker holding the GIL or a long synchronous call
kept every connection waiting (METRICS ``rtpu_host_loop_long_stalls_total``,
after minus before; counted while tracing is armed)."""
from benchmark import counters


def read(obs):
    return counters.delta(obs, "rtpu_host_loop_long_stalls_total")
