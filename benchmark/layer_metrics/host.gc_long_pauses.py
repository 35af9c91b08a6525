"""Host: collections of 40 ms or more during the window — the size of the
stalls that set the tail (METRICS ``rtpu_host_gc_long_pauses_total``, after
minus before; counted while tracing is armed).  0 beside long loop stalls
clears the collector."""
from benchmark import counters


def read(obs):
    return counters.delta(obs, "rtpu_host_gc_long_pauses_total")
