"""Host: milliseconds by which the server's event loop woke late during the
window, summed over wake-ups 5 ms or more late (the loop's 10 ms heartbeat;
METRICS ``rtpu_host_loop_stall_seconds_total``, after minus before; counted
while tracing is armed)."""
from benchmark import counters


def read(obs):
    return counters.delta(obs, "rtpu_host_loop_stall_seconds_total", 1e3)
